"""GPipe-style pipeline parallelism over a mesh axis (the multi-pod `pod`
axis option).

SPMD formulation: every stage runs the same program; a microbatch ripples
through stages via ``collective_permute`` (shift +1 on the pipeline axis)
once per tick, for ``n_micro + n_stages - 1`` ticks.  Stage 0 injects
microbatch t at tick t; stage S-1 emits the result of microbatch t at tick
t + S - 1.  Differentiable end-to-end (collective_permute transposes to the
reverse shift), so training composes with jax.grad.

This is the mechanism module: ``pipeline_apply`` pipelines any per-stage
function ``stage_fn(stage_params, x) -> x`` whose per-stage params carry a
leading stage dimension sharded over the pipeline axis.  The multi-pod
default keeps `pod` as pure DP; flip to PP by sharding the layer stack's
leading dim over `pod` and wrapping the stack with this.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x_micro, *, mesh, axis="pod"):
    """Run x through n_stages sequential stage_fns, pipelined over microbatches.

    stage_fn: (stage_params_local, x (mb, ...)) -> y (mb, ...)
    stage_params: pytree, leaves (n_stages, ...) — sharded over `axis`.
    x_micro: (n_micro, mb, ...) microbatched input (replicated over `axis`).
    Returns (n_micro, mb, ...) outputs (replicated over `axis`).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def shard_fn(stage_params_local, x_micro):
        # stage_params_local leaves: (1, ...) — this stage's slice
        sp = jax.tree.map(lambda v: v[0], stage_params_local)
        stage = jax.lax.axis_index(axis)

        def tick(carry, t):
            x_in, outs = carry
            mb_in = jnp.clip(t, 0, n_micro - 1)
            x0 = x_micro[mb_in]
            # stage 0 consumes a fresh microbatch; others take the permuted
            # predecessor output.
            x = jnp.where(stage == 0, x0, x_in)
            y = stage_fn(sp, x)
            # ship to the next stage (stage S-1 -> 0 wraps; ignored there)
            x_next = jax.lax.ppermute(y, axis, perm)
            # last stage: record microbatch (t - (n_stages - 1))
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (stage == n_stages - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_idx, 0, n_micro - 1), 0),
                lambda o: o, outs)
            return (x_next, outs), None

        x0 = jnp.zeros_like(x_micro[0])
        outs0 = jnp.zeros_like(x_micro)
        (_, outs), _ = jax.lax.scan(tick, (x0, outs0),
                                    jnp.arange(ticks))
        # everyone returns outs; only the last stage's is real — broadcast it
        # (masked psum: a source may appear only once in a ppermute).
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params,
                             is_leaf=lambda v: hasattr(v, "shape")),
                P())
    return jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs,
        out_specs=P(), check_vma=False)(stage_params, x_micro)

"""Runs under XLA_FLAGS=--xla_force_host_platform_device_count=8 (spawned by
test_distributed.py).  Verifies numerical equivalence of the distributed paths
against single-logical-device references:

  1. shard_map MoE (EP over `model`) == local dense-capacity MoE
  2. fully sharded train loss/grad step == unsharded step
  3. decode with a seq-sharded KV cache == unsharded decode
"""
import os

assert "--xla_force_host_platform_device_count=8" in os.environ.get(
    "XLA_FLAGS", ""), "spawn me via test_distributed.py"

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.models import blocks, lm
from repro.models.blocks import NULL_PROFILE, ShardProfile

assert jax.device_count() == 8, jax.device_count()

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
prof = ShardProfile(mesh=mesh, tp="model", fsdp=None, dp=("data",), tp_size=4)


def check(name, a, b, tol=2e-3):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    err = np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-6)
    assert err < tol, (name, err)
    print(f"[distributed_check] {name}: rel_err={err:.2e} OK", flush=True)


# --------------------------------------------------------------- 1. MoE EP
# capacity_factor high enough that no tokens drop: dropping is shard-local
# (matches real EP fleets) so dropped-token sets differ between the 1-shard
# reference and the 2-data-shard run; equivalence holds in the no-drop regime.
cfg = dataclasses.replace(smoke_config("kimi-k2-1t-a32b"), n_experts=8,
                          top_k=2, dtype="float32", capacity_factor=8.0)
key = jax.random.PRNGKey(0)
pm, sm = blocks.init_moe(key, cfg, jnp.float32, NULL_PROFILE)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)

out_local, aux_local = blocks.apply_moe(pm, x, cfg, NULL_PROFILE)
out_dist, aux_dist = jax.jit(
    lambda p, x: blocks.apply_moe(p, x, cfg, prof))(pm, x)
check("moe.out", out_dist, out_local)
# load-balance aux uses per-shard statistics (mean over shards of per-shard
# E*sum(me*ce) != global joint statistic) — standard distributed-MoE practice;
# it's a training heuristic, so only loose agreement is required.
check("moe.load_balance", aux_dist["load_balance"],
      aux_local["load_balance"], tol=0.2)

# MoE with sequence-sharded residual stream: reduce-scatter combine path
prof_sp = dataclasses.replace(prof, seq="model")
out_sp, _ = jax.jit(lambda p, x: blocks.apply_moe(p, x, cfg, prof_sp))(pm, x)
check("moe.out.seq_sharded_scatter", out_sp, out_local)

# ------------------------------------------------- 2. sharded train step
cfg2 = dataclasses.replace(smoke_config("kimi-k2-1t-a32b"),
                           capacity_factor=8.0)
params, specs = lm.init_params(jax.random.PRNGKey(2), cfg2, prof)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                                      cfg2.vocab)}


def loss_sharded(p):
    return lm.loss_fn(p, cfg2, batch, prof, scan_method="chunked")[0]


def loss_plain(p):
    return lm.loss_fn(p, cfg2, batch, NULL_PROFILE, scan_method="chunked")[0]


p_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                    is_leaf=lambda v: isinstance(v, P))
params_d = jax.device_put(params, p_sh)
l_sharded, g_sharded = jax.jit(jax.value_and_grad(loss_sharded))(params_d)
l_plain, g_plain = jax.jit(jax.value_and_grad(loss_plain))(params)
check("train.loss", l_sharded, l_plain)
# grads agree up to the per-shard load-balance aux statistic (x0.01 coeff in
# the loss) — the nll path itself matches at ~1e-4.
for (ka, va), (kb, vb) in zip(
        sorted(jax.tree_util.tree_leaves_with_path(g_sharded),
               key=lambda t: str(t[0]))[:6],
        sorted(jax.tree_util.tree_leaves_with_path(g_plain),
               key=lambda t: str(t[0]))[:6]):
    check(f"train.grad.{jax.tree_util.keystr(ka)}", va, vb, tol=2.5e-2)

# ------------------------------------------------- 3. seq-sharded decode
cfg3 = smoke_config("qwen2-72b")
p3, s3 = lm.init_params(jax.random.PRNGKey(4), cfg3, prof)
cache = lm.make_decode_cache(p3, cfg3, 4, 32, prof)
c_specs = lm.cache_specs(cfg3, prof)
c_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), c_specs,
                    is_leaf=lambda v: isinstance(v, P))
tok = jnp.ones((4, 1), jnp.int32)

lg_plain, cache_p = lm.decode_step(p3, cfg3, cache, tok, NULL_PROFILE)
lg2_plain, _ = lm.decode_step(p3, cfg3, cache_p, tok + 1, NULL_PROFILE)

p3_d = jax.device_put(p3, jax.tree.map(
    lambda sp: NamedSharding(mesh, sp), s3,
    is_leaf=lambda v: isinstance(v, P)))
cache_d = jax.device_put(cache, c_sh)
step = jax.jit(lambda p, c, t: lm.decode_step(p, cfg3, c, t, prof),
               in_shardings=(None, c_sh, None), out_shardings=(None, c_sh))
lg_dist, cache_d = step(p3_d, cache_d, tok)
lg2_dist, _ = step(p3_d, cache_d, tok + 1)
check("decode.logits.t0", lg_dist, lg_plain)
check("decode.logits.t1", lg2_dist, lg2_plain)

print("[distributed_check] ALL OK", flush=True)

# ------------------------------------------------- 4. pipeline parallelism
from repro.train.pipeline import pipeline_apply

mesh_pp = jax.make_mesh((4, 2), ("pod", "model"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 2)
rngk = jax.random.PRNGKey(7)
n_stages, n_micro, mb, dd = 4, 6, 3, 16
ws = jax.random.normal(rngk, (n_stages, dd, dd)) * 0.3


def stage_fn(w, x):
    return jnp.tanh(x @ w)


x_micro = jax.random.normal(jax.random.PRNGKey(8), (n_micro, mb, dd))
# reference: sequential stages
ref = x_micro
for s in range(n_stages):
    ref = jax.vmap(lambda xb: stage_fn(ws[s], xb))(ref)
got = pipeline_apply(stage_fn, ws, x_micro, mesh=mesh_pp, axis="pod")
check("pipeline.forward", got, ref)

# differentiability: grad of a scalar loss through the pipeline
def loss_pp(ws):
    return jnp.sum(pipeline_apply(stage_fn, ws, x_micro, mesh=mesh_pp,
                                  axis="pod") ** 2)


def loss_ref(ws):
    y = x_micro
    for s in range(n_stages):
        y = jax.vmap(lambda xb, s=s: stage_fn(ws[s], xb))(y)
    return jnp.sum(y ** 2)


g_pp = jax.grad(loss_pp)(ws)
g_rf = jax.grad(loss_ref)(ws)
check("pipeline.grad", g_pp, g_rf)

print("[distributed_check] ALL OK (incl. pipeline)", flush=True)

# ------------------------------------------ 5. distributed ridge (the paper)
# EET readout training at fleet scale: shards accumulate local Gram stats,
# ONE psum finishes the job (O(N'^2) bytes regardless of sequence length).
from repro.core import ridge as ridge_mod

t_total, nf = 512, 24
xs = jax.random.normal(jax.random.PRNGKey(9), (t_total, nf))
ys = jax.random.normal(jax.random.PRNGKey(10), (t_total, 1))
g_full, c_full = ridge_mod.gram(xs, ys)


def shard_gram(x, y):
    g, c = ridge_mod.gram(x, y)
    return jax.lax.psum(g, "data"), jax.lax.psum(c, "data")


g_d, c_d = jax.shard_map(
    shard_gram, mesh=mesh, in_specs=(P("data", None), P("data", None)),
    out_specs=(P(), P()), check_vma=False)(xs, ys)
check("ridge.gram_psum", g_d, g_full, tol=1e-5)
w_full = ridge_mod.ridge_solve(g_full, c_full, 1e-3)
w_dist = ridge_mod.ridge_solve(g_d, c_d, 1e-3)
check("ridge.weights", w_dist, w_full, tol=1e-4)

print("[distributed_check] ALL OK (complete)", flush=True)

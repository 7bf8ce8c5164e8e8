"""SlotArena — the device-side layer of the serving stack.

The serving stack is three layers (bottom to top):

* **arena** (this module) — the ``(B, N)`` slot state itself, as an immutable
  registered pytree plus *pure functions* over it.  Nothing here knows about
  sessions, queues, or admission policy; everything is jit/vmap/device_put
  friendly, so one arena can be placed on a multi-device mesh
  (``sharding.rules.plan_arena``: slots on the ``data`` axis, N on the
  ``model`` axis — the diag step is element-wise, so the state shards
  trivially).
* **scheduler** (``serve.scheduler``) — host-side admission: requests are
  bucketed by padded prompt length and served in waves.
* **engine** (``serve.engine``) — the thin orchestrator that owns the
  session <-> slot mapping and calls down into both.

The heart of the layer is :func:`prefill_wave`: ONE ``(B_wave, T_bucket)``
batched scan (backend from ``core.dispatch``) replaces ``B_wave`` sequential
per-session prefills.  Rows are padded up to the bucket length; because the
recurrence is causal, the padded tail steps can never influence the gathered
per-row final state ``states[b, length_b - 1]`` — the padding is provably
inert (pinned by test), so rows of different true lengths share one trace.

All functions take the param struct (``core.params``) and readout ``w_out``
as explicit arguments.  ``batched=True`` means a *stacked* param struct
(``stack_params``): slot ``i`` runs reservoir ``i``, sliced out of the stack
inside the trace.  ``ensemble="mean"`` reduces the per-slot predictions of a
param-batched arena to one ensemble output that is also what feeds back in
closed loop (state feedback per Ehlers et al. 2023 stays bit-exact: the
feedback column simply carries the ensemble mean instead of the per-slot
prediction).

**Aliasing under the pipelined executor.**  Every function here is
value-semantic: it returns a *new* ``SlotArena`` whose arrays share no
mutable storage with the input's (XLA buffers are immutable unless
donated).  The engine's pipelined executor leans on that: while a wave is
in flight it may gather page-out rows from the *pre-wave* arena value —
legal precisely because the older value is a live, unaliased buffer whose
untouched rows are bit-identical to the post-wave value (scatters only
write their own slots).  The ONE exception is donation: when the engine
compiles its wave step with ``donate_argnums`` (TPU), the input arena's
buffers may be reused in place by XLA, so a superseded arena value must
never be read again — the engine gates the fast path off under donation
(see ``ReservoirEngine._demote_wave``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..core import dispatch as dispatch_mod
from ..core import esn as esn_fn

__all__ = [
    "SlotArena",
    "make_arena",
    "place",
    "place_many",
    "gather_rows",
    "release_many",
    "force_output",
    "arena_step",
    "apply_readout",
    "decode_step",
    "driven_loop",
    "closed_loop",
    "closed_loop_fused",
    "prefill_wave",
]


@dataclasses.dataclass(frozen=True)
class SlotArena:
    """Device-side slot state: the one owner of the raw serving arrays.

    ``states``: (B, N) recurrent state in the model's native basis (Q basis
    for diag models); ``y_prev``: (B, D_out) last output per slot (the
    feedback column); ``active``: (B,) bool occupancy mask — a device-side
    mirror of the engine's host-side slot table, which is authoritative.
    The compute functions take explicit per-call ``mask`` arguments (which
    sessions to step is policy, decided host-side), so no serving step reads
    ``active``.  Releasing a session writes nothing here: the mirror is
    written whole from the table by the next wave placement
    (:func:`place_many`) and by every read through the engine
    (``ReservoirEngine.arena``), so it is exact after every wave placement
    and wherever it is read (checkpointing a whole arena, debug dumps).
    """
    states: jnp.ndarray
    y_prev: jnp.ndarray
    active: jnp.ndarray

    @property
    def max_slots(self) -> int:
        return self.states.shape[0]


jax.tree_util.register_dataclass(SlotArena,
                                 ["states", "y_prev", "active"], [])


def make_arena(n: int, d_out: int, max_slots: int, dtype) -> SlotArena:
    """A zeroed arena of ``max_slots`` slots, all free."""
    return SlotArena(states=jnp.zeros((max_slots, n), dtype),
                     y_prev=jnp.zeros((max_slots, d_out), dtype),
                     active=jnp.zeros((max_slots,), bool))


def place(arena: SlotArena, slot: int, h0, y0) -> SlotArena:
    """Write a session's (state, feedback) into ``slot`` and mark it live."""
    return SlotArena(states=arena.states.at[slot].set(h0),
                     y_prev=arena.y_prev.at[slot].set(y0),
                     active=arena.active.at[slot].set(True))


def place_many(arena: SlotArena, slots, h0s, y0s, occupied) -> SlotArena:
    """Write a whole wave of sessions in ONE scatter per array — per-slot
    ``place`` calls would cost 3 device dispatches each, which at wave sizes
    dwarfs the batched prefill itself on CPU.  ``occupied``: the (B,) bool
    occupancy of the host slot table, the wave's slots included; it
    overwrites the whole ``active`` mirror, so slots freed since the last
    write are marked free by this same dispatch."""
    return SlotArena(states=arena.states.at[slots].set(h0s),
                     y_prev=arena.y_prev.at[slots].set(y0s),
                     active=arena.active.at[:].set(occupied))


def gather_rows(arena: SlotArena, slots):
    """Lazy device slices of ``slots``'s (states, y_prev) rows — the gather
    half of a demote page wave.  Returns device arrays (no host sync): the
    caller picks when to pay the transfer (``jax.device_get``).  Safe to
    call on a *superseded* arena value (the pipelined demote fast path) as
    long as that value was not donated — see the module docstring."""
    idx = jnp.asarray(slots)
    return arena.states[idx], arena.y_prev[idx]


def release_many(arena: SlotArena, slots) -> SlotArena:
    """Free a whole wave of slots in ONE scatter — the demote half of a page
    wave (``serve.store``): the engine gathers the victims' rows with one
    ``device_get`` and then frees all their slots here.  The state arrays
    are left in place: the victims' rows were gathered first, and a freed
    slot's row is overwritten by its next placement."""
    return SlotArena(states=arena.states, y_prev=arena.y_prev,
                     active=arena.active.at[slots].set(False))


def force_output(arena: SlotArena, slot: int, y_true) -> SlotArena:
    """Teacher-force ``slot``: overwrite its feedback output ``y_prev[slot]``
    with ground truth, leaving the recurrent state untouched.  The next
    ``decode_step`` / ``closed_loop`` of that slot then drives from the true
    output instead of the model's own prediction — the open-loop serving
    correction (``ReservoirEngine.observe``).  Returns the rebuilt arena;
    like every function here it never mutates, so the caller must store the
    result (dropping it is the silent-no-op bug this API exists to avoid).
    """
    return dataclasses.replace(arena,
                               y_prev=arena.y_prev.at[slot].set(y_true))


# ------------------------------------------------------------------ stepping
def arena_step(params, states, u, y_prev, *, batched: bool = False):
    """One reservoir step over the whole slot block.  Shared params broadcast
    over (B, N); a param *batch* vmaps — one trace, B distinct reservoirs."""
    fb = params.cfg.use_feedback
    if batched:
        def one(p, h, ui, yi):
            return esn_fn.step_states(
                p, h, esn_fn.drive(p, ui, yi if fb else None))
        return jax.vmap(one)(params, states, u, y_prev)
    return esn_fn.step_states(
        params, states, esn_fn.drive(params, u, y_prev if fb else None))


def apply_readout(w_out, x, *, batched: bool = False):
    """Per-slot readouts are inferred from shape: a (B, F, D) ``w_out`` pairs
    row ``b`` of ``x`` with readout ``b`` even when the reservoir params are
    shared (per-tenant readout pools over one arena) — a plain ``x @ w_out``
    there would contract the wrong axes."""
    if batched or w_out.ndim == 3:
        return jnp.einsum("bf,bfd->bd", x, w_out)
    return x @ w_out


def _ensemble_reduce(y, mask, weights=None):
    """(Weighted) mean over the stepped slots, broadcast back to every row.
    ``weights=None`` is the plain mean; otherwise per-slot voting weights
    (validation-RMSE-derived), renormalized over the masked slots."""
    if weights is None:
        w = mask
        denom = jnp.maximum(jnp.sum(mask), 1)
    else:
        w = jnp.asarray(weights, y.dtype) * mask
        denom = jnp.maximum(jnp.sum(w), jnp.asarray(1e-9, y.dtype))
    y_mean = jnp.sum(y * w[:, None], axis=0) / denom
    return jnp.broadcast_to(y_mean, y.shape)


def decode_step(params, w_out, arena: SlotArena, u, mask, ens_weights=None, *,
                batched: bool = False, ensemble: str = "off"):
    """Advance the masked slots one token.  Returns ``(arena', y)`` where
    unmasked rows of ``y`` hold their previous output."""
    new = arena_step(params, arena.states, u, arena.y_prev, batched=batched)
    states = jnp.where(mask[:, None], new, arena.states)
    if w_out is None:
        return dataclasses.replace(arena, states=states), arena.y_prev
    x = esn_fn.assemble_features(params, states, arena.y_prev)
    y = apply_readout(w_out, x, batched=batched)
    if ensemble == "mean":
        y = _ensemble_reduce(y, mask)
    elif ensemble == "weighted":
        y = _ensemble_reduce(y, mask, ens_weights)
    y_out = jnp.where(mask[:, None], y, arena.y_prev)
    return dataclasses.replace(arena, states=states, y_prev=y_out), y_out


def driven_loop(params, w_out, arena: SlotArena, mask, u_seq,
                ens_weights=None, *, batched: bool = False,
                ensemble: str = "off"):
    """Teacher-driven generation over the masked slots: step K queued inputs
    ``u_seq`` of shape (K, B, D_in) through the arena in ONE dispatch.  Each
    scan step is exactly :func:`decode_step` on ``u_seq[t]``, so draining a
    per-session input queue this way is bit-identical to K sequential
    ``decode_step`` calls.  Returns ``(arena', ys)`` with ``ys`` of shape
    (K, B, D_out)."""
    w_ens = ens_weights if ensemble == "weighted" else None

    def step(carry, u_t):
        states, y = carry
        new = arena_step(params, states, u_t, y, batched=batched)
        states = jnp.where(mask[:, None], new, states)
        x = esn_fn.assemble_features(params, states, y)
        y_new = apply_readout(w_out, x, batched=batched)
        if ensemble in ("mean", "weighted"):
            y_new = _ensemble_reduce(y_new, mask, w_ens)
        y_new = jnp.where(mask[:, None], y_new, y)
        return (states, y_new), y_new

    (states, y_prev), ys = jax.lax.scan(
        step, (arena.states, arena.y_prev), u_seq)
    return dataclasses.replace(arena, states=states, y_prev=y_prev), ys


def closed_loop(params, w_out, arena: SlotArena, mask, n_steps: int,
                ens_weights=None, *, batched: bool = False,
                ensemble: str = "off"):
    """Free-running generation over the masked slots: each step feeds the
    prediction (or the ensemble mean of the predictions) back as the next
    input.  Returns ``(arena', ys)`` with ``ys`` of shape (n_steps, B, D_out).
    """
    w_ens = ens_weights if ensemble == "weighted" else None

    def step(carry, _):
        states, y = carry
        new = arena_step(params, states, y, y, batched=batched)
        states = jnp.where(mask[:, None], new, states)
        x = esn_fn.assemble_features(params, states, y)
        y_new = apply_readout(w_out, x, batched=batched)
        if ensemble in ("mean", "weighted"):
            y_new = _ensemble_reduce(y_new, mask, w_ens)
        y_new = jnp.where(mask[:, None], y_new, y)
        return (states, y_new), y_new

    y0 = arena.y_prev
    if ensemble in ("mean", "weighted"):
        # The free-run starts from the fused seed too: every masked
        # reservoir's first closed-loop input is the ensemble reduce of the
        # stepped slots' seeds (unmasked slots keep their own y_prev).
        y0 = jnp.where(mask[:, None], _ensemble_reduce(y0, mask, w_ens), y0)
    (states, y_prev), ys = jax.lax.scan(
        step, (arena.states, y0), None, length=n_steps)
    return dataclasses.replace(arena, states=states, y_prev=y_prev), ys


def closed_loop_fused(params, w_out, arena: SlotArena, mask, n_steps: int,
                      ens_weights=None, *, batched: bool = False,
                      ensemble: str = "off", method: str = "auto"):
    """:func:`closed_loop` through the fused K-token decode kernel: one
    dispatch runs all ``n_steps`` (diag step + readout + ensemble reduce +
    feedback write) with the carry resident on-device
    (``core.dispatch.run_decode_fused`` — Pallas on TPU, the jnp reference
    elsewhere).  Same signature, same ``(arena', ys)`` contract; dense-mode
    params, a missing readout, or weighted-ensemble voting (the kernel only
    reduces by plain mean) fall back to the scan path (where ``batched``
    still applies — the fused path infers it from ``lam_q.ndim``).
    """
    if w_out is None or params.mode != "diag" or ensemble == "weighted":
        return closed_loop(params, w_out, arena, mask, n_steps, ens_weights,
                           batched=batched, ensemble=ensemble)
    cfg = params.cfg
    w_drive = (params.win_q + params.wfb_q if cfg.use_feedback
               else params.win_q)
    states, y_prev, ys = dispatch_mod.run_decode_fused(
        params.lam_q, params.n_real, w_drive, w_out, arena.states,
        arena.y_prev, mask, int(n_steps), use_bias=cfg.use_bias,
        use_feedback=cfg.use_feedback, ensemble=ensemble, method=method)
    return dataclasses.replace(arena, states=states, y_prev=y_prev), ys


# ------------------------------------------------------------- wave prefill
def _rows_prefill(params, w_out, cfg, h0, y0, u, y_teacher, lengths, *,
                  method: str, chunk: int, want_outputs: bool):
    """Prefill a block of rows of a wave: ONE scan of the padded
    (B, T_bucket, D_in) prompts, then gather each row's state/output at its
    true last step.

    The scan runs over the full padded length, but the recurrence is causal:
    nothing at t >= length can reach ``states[length - 1]``, so the gathered
    final state (and the y_prev seed) are exactly what an unpadded prefill
    produces — padding is inert by construction, not by masking arithmetic.
    Per-step outputs past the true length are zeroed.  ``w_out`` is one
    (F, D) readout or a (B, F, D) readout per row.
    """
    y_shift = None
    if cfg.use_feedback:
        y_shift = jnp.concatenate([y0[:, None], y_teacher[:, :-1]], axis=1)
    states = esn_fn.scan_states(params, esn_fn.drive(params, u, y_shift),
                                h0, method=method, chunk=chunk)
    last_idx = (lengths - 1)[:, None, None]

    def at_last(v):
        return jnp.take_along_axis(v, last_idx, axis=1)[:, 0]

    last = at_last(states)
    valid = (jnp.arange(u.shape[1])[None, :] < lengths[:, None])[..., None]
    if cfg.use_feedback:
        # Prefill is teacher-forced end-to-end: the teacher's last *true*
        # output is the feedback seed (parity with core.esn.run).
        y_next = at_last(y_teacher)
    if w_out is None:
        out = jnp.where(valid, states, 0) if want_outputs else None
        return last, (y_next if cfg.use_feedback else y0), out
    y_last = None
    if want_outputs:
        x = esn_fn.assemble_features(params, states, y_shift)
        y = (x @ w_out if w_out.ndim == 2
             else jnp.einsum("btf,bfd->btd", x, w_out))
        out = jnp.where(valid, y, 0)
        if not cfg.use_feedback:         # feedback models seed from y_next
            y_last = at_last(y)
    else:
        # Last-step readout only: O(N) — just the closed-loop feedback seed
        # (feedback models need none: the teacher's last output wins).
        out = None
        if not cfg.use_feedback:
            y_last = apply_readout(
                w_out, esn_fn.assemble_features(params, last, None))
    return last, (y_next if cfg.use_feedback else y_last), out


def prefill_wave(params, w_out, arena: SlotArena, slots, u, lengths,
                 y_teacher=None, *, batched: bool = False,
                 method: str = "sequential", chunk: int = 128,
                 want_outputs: bool = True):
    """Run ONE batched prefill over a wave of slots.

    ``slots``: (B_wave,) slot indices; ``u``: (B_wave, T_bucket, D_in)
    prompts padded to the bucket length; ``lengths``: (B_wave,) true prompt
    lengths; ``y_teacher``: (B_wave, T_bucket, D_out) teacher outputs for
    feedback models (padding rows past ``lengths`` are ignored).

    One scan serves the whole wave — with shared params the rows ride as the
    batch axis of a single (B_wave, T_bucket, N) scan (one kernel launch on
    the Pallas path); with a param batch a ``vmap`` slices each row's own
    reservoir out of the stack.  Returns
    ``(arena', outputs)`` where outputs is (B_wave, T_bucket, D_out)
    per-step predictions ((B_wave, T_bucket, N) states when ``w_out`` is
    None), zeroed past each row's true length, or None when
    ``want_outputs=False``.

    **Resumable carry**: every row starts from its slot's *current*
    ``(states[slot], y_prev[slot])`` and writes the post-scan carry back, so
    running a prompt as K sequential same-slot waves over its chunks is
    numerically identical to one wave over the whole prompt — chunk k+1's
    ``h0`` is chunk k's gathered final state, and for feedback models chunk
    k+1's ``y0`` is chunk k's last true teacher output (exactly the
    ``y_shift`` element the unchunked scan would use at that step).  The
    scheduler's chunked long-prompt waves (``WaveScheduler(chunk_max=...)``)
    ride this path; bit-parity vs the unchunked wave is pinned by test.

    ``method`` is static: the engine resolves it host-side from the bucket
    length (``core.dispatch.resolve_method``), so every wave of a bucket
    reuses one compiled trace.
    """
    cfg = params.cfg
    h0 = arena.states[slots]
    y0 = arena.y_prev[slots]
    kw = dict(method=method, chunk=chunk, want_outputs=want_outputs)

    if batched:
        # Row b runs reservoir ``slots[b]``: slice it out of the stack and
        # prefill each row as a block of one.
        def one(slot, h0_r, y0_r, u_r, yt_r, length):
            p = jax.tree_util.tree_map(
                lambda leaf: jax.lax.dynamic_index_in_dim(
                    leaf, slot, keepdims=False), params)
            wo = (None if w_out is None else
                  jax.lax.dynamic_index_in_dim(w_out, slot, keepdims=False))
            yt = None if yt_r is None else yt_r[None]
            last, y_next, out = _rows_prefill(
                p, wo, cfg, h0_r[None], y0_r[None], u_r[None], yt,
                length[None], **kw)
            return last[0], y_next[0], None if out is None else out[0]

        if y_teacher is None:
            last, y_next, out = jax.vmap(
                lambda s, h, y, ur, ln: one(s, h, y, ur, None, ln))(
                    slots, h0, y0, u, lengths)
        else:
            last, y_next, out = jax.vmap(one)(slots, h0, y0, u, y_teacher,
                                              lengths)
    else:
        # Shared reservoir: the whole wave is one (B_wave, T_bucket) scan.
        # A per-slot readout pool (B, F, D) serves each row its own readout.
        wo = w_out[slots] if w_out is not None and w_out.ndim == 3 else w_out
        last, y_next, out = _rows_prefill(params, wo, cfg, h0, y0, u,
                                          y_teacher, lengths, **kw)
    arena = dataclasses.replace(
        arena,
        states=arena.states.at[slots].set(last),
        y_prev=arena.y_prev.at[slots].set(y_next))
    return arena, out

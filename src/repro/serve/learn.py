"""Learn plane — streaming Gram accumulation, batched refit waves, the
per-tenant readout pool entries, and drift-triggered DPG ensemble growth.

The engine is a training system too (``learn=True``): every ``observe()``
teacher token both corrects the feedback column AND accumulates the
session's eigenbasis Gram sufficient statistics ``(G, C)``
(``core.ridge.gram_streaming`` rows, λ-decayed so old regimes fade);
:meth:`LearnPlane.refit_wave` solves ``ridge_solve_general(G, C,
eet_metric, α)`` for every dirty session as ONE batched device wave.  When
a session's held-out streaming RMSE drifts past ``drift_threshold``, a
fresh ``dpg_params`` reservoir member is sampled on-demand (DPG: O(N), no
diagonalization) and folded into that session's ensemble with
validation-RMSE-weighted voting.

Layering: this module imports only ``core`` and ``serve.arena`` — never
the exec/ingest planes or the engine facade (enforced by
tests/test_serving_planes.py).  Cross-plane effects (scattering refit
results into the device-side slot pool, charging the decode budget) go
through callbacks the facade wires at construction: the plane never
reaches upward on its own.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Hashable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import esn as esn_fn
from ..core import ridge as ridge_mod
from ..core.dispatch import device_fn
from . import arena as arena_mod

__all__ = ["LearnPlane", "_GramAcc", "_Member", "_LearnState"]


@dataclasses.dataclass
class _GramAcc:
    """Streaming sufficient statistics for one readout: the folded
    eigenbasis Gram pair ``(G, C)`` plus the not-yet-folded row buffers
    (lazy device slices — folding pays the stack/matmul in one chunk at
    refit time, never per token) and the held-out drift EWMA buffers
    (pre-observe prediction vs truth — prequential, so the 'validation'
    set is every teacher token *before* it trains)."""
    gram: Optional[object] = None           # folded (F, F) device array
    cg: Optional[object] = None             # folded (F, D_out) device array
    pairs: int = 0                          # rows folded so far
    skip_left: int = 0                      # washout rows still to discard
    drift: Optional[float] = None           # EWMA of held-out squared error
    buf_h: List = dataclasses.field(default_factory=list)
    buf_fb: List = dataclasses.field(default_factory=list)
    buf_y: List = dataclasses.field(default_factory=list)
    buf_pred: List = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Member:
    """A DPG-grown ensemble member: its own freshly sampled reservoir
    (``core.esn.dpg_params`` — O(N), no diagonalization) advancing in
    lock-step with the session's teacher stream from ``h=0`` (the echo
    state property synchronizes it), plus its own :class:`_GramAcc`.  Its
    readout ``w`` stays None (no vote) until the first refit wave solves
    it from enough accumulated pairs."""
    params: object
    h: object                               # (N,) member state
    y_fb: object                            # member's own feedback column
    w: Optional[object] = None              # (F, D_out) once refit-trained
    steps_since_fb: int = 0
    pred_last: Optional[object] = None
    acc: _GramAcc = dataclasses.field(default_factory=_GramAcc)
    metric: Optional[object] = None         # cached EET metric (params-const)


@dataclasses.dataclass
class _LearnState:
    """Per-session learn-while-serving state (host-side, plane-owned — it
    does NOT travel through the session store: a parked session keeps its
    accumulated ``(G, C)`` exactly like it keeps its un-collected decode
    buffer).  ``steps_since_fb`` gates accumulation: a feature row is only
    a valid training pair when exactly ONE decode step ran since the last
    teacher token (free-running tokens in between would pair a state with
    a truth it never saw)."""
    tenant: Optional[Hashable] = None
    last_fb: Optional[np.ndarray] = None    # teacher value forced last
    steps_since_fb: int = 0
    dirty: bool = False
    acc: _GramAcc = dataclasses.field(default_factory=_GramAcc)
    members: List = dataclasses.field(default_factory=list)


def _fold_rows_core(params, h, fb, y, g0, c0, lam):
    """One-dispatch refit fold: assemble the feature rows, apply the
    λ-decay row weights, accumulate the (G, C) Gram pair, and (when prior
    stats exist) decay-combine them — fused so a warm refit wave pays one
    kernel instead of a chain of eager ops.  ``fb``/``g0`` being None
    selects a second trace (None is a static pytree), and the window
    length m recompiles by shape — constant at serve cadence."""
    x = esn_fn.assemble_features(params, h, fb)
    m = x.shape[0]
    if lam < 1.0:
        w = lam ** (jnp.arange(m - 1, -1, -1, dtype=x.dtype) / 2.0)
        x = x * w[:, None]
        y = y * w[:, None]
    g, c = ridge_mod.gram_streaming(x, y)
    if g0 is not None:
        decay = lam ** m
        g = decay * g0 + g
        c = decay * c0 + c
    return g, c


_fold_rows = functools.partial(jax.jit, static_argnames=("lam",))(
    device_fn(_fold_rows_core))


@functools.partial(jax.jit, static_argnames=("lam",))
@device_fn
def _fold_rows_batch(params, h, fb, y, g0, c0, lam):
    """The same fold vmapped over sessions (shared params): a refit wave
    whose dirty sessions share one window length — the steady serve
    cadence — folds them all in ONE dispatch instead of one per session."""
    return jax.vmap(lambda hh, ff, yy, gg, cc:
                    _fold_rows_core(params, hh, ff, yy, gg, cc, lam)
                    )(h, fb, y, g0, c0)


class LearnPlane:
    """Owns every learn-while-serving structure: the per-session
    :class:`_LearnState` table, the per-tenant readout-pool *entries*
    (the device-side per-slot gather lives in the exec plane), the batched
    refit solver, and the acc cache decode_step snapshots for observe().

    Facade-wired callbacks (never imported): ``session_slot(sid)`` resolves
    a hot session's slot, ``activate_pool()`` / ``sync_readouts(pairs)``
    scatter refit results into the exec plane's device pool,
    ``hot_serving(keys)`` lists the hot (sid, slot) pairs serving any of
    ``keys``, and ``charge(us)`` bills wave cost to the decode deadlines.
    """

    def __init__(self, params, cfg, dtype, *, batched: bool, enabled: bool,
                 tracker, refit_alpha: float, refit_decay: float,
                 refit_washout: int, drift_threshold: Optional[float],
                 drift_beta: float, growth_max: int, growth_sigma: float,
                 growth_washout: int, cost_model=None, autotune: bool = False):
        self.params = params
        self.cfg = cfg
        self._dtype = dtype
        self._batched = bool(batched)
        self.enabled = bool(enabled)
        self.tracker = tracker
        self.cost_model = cost_model
        self._autotune = bool(autotune)
        self._refit_alpha = float(refit_alpha)
        self._refit_decay = float(refit_decay)
        self._refit_washout = int(refit_washout)
        self._drift_threshold = (None if drift_threshold is None
                                 else float(drift_threshold))
        self._drift_beta = float(drift_beta)
        self._growth_max = int(growth_max)
        self._growth_sigma = float(growth_sigma)
        self._growth_washout = int(growth_washout)
        self._growth_seed = int(getattr(cfg, "seed", 0) or 0) + 7001
        self.state: Dict[Hashable, _LearnState] = {}
        self.readouts: Dict[Hashable, object] = {}
        self._metric_cache: Dict[Hashable, object] = {}
        self._acc_cache = None          # (states_ref, states_np, y_prev_np)
        # Batched refit: ONE vmapped generalized ridge solve covers every
        # dirty session (and grown member) in a wave — (R, F, F) Grams,
        # (R, F, D) cross terms, (R, F, F) per-row metrics (EET
        # blockdiag(I, QᵀQ) for diag rows, identity for standard), shared
        # traced alpha.
        self._refit_jit = jax.jit(device_fn(jax.vmap(
            ridge_mod.ridge_solve_general, in_axes=(0, 0, 0, None))))
        # Facade-wired cross-plane callbacks (see class docstring).
        self.session_slot = lambda sid: None
        self.activate_pool = lambda: None
        self.sync_readouts = lambda pairs: None
        self.hot_serving = lambda keys: []
        self.charge = lambda us: None

    # ------------------------------------------------------- session table
    def note_admission(self, sid, tenant) -> None:
        """Create the session's learn state at admission (lazy: an engine
        with ``learn=False`` and no tenant key never allocates one)."""
        if tenant is None and not self.enabled:
            return
        ls = self.state.setdefault(sid, _LearnState())
        if tenant is not None:
            ls.tenant = tenant
        if ls.acc.pairs == 0 and not ls.acc.buf_h:
            ls.acc.skip_left = self._refit_washout

    def pop(self, sid) -> None:
        self.state.pop(sid, None)

    def export_state(self, hot) -> Dict[Hashable, _LearnState]:
        """Every session's learn state with its buffered rows folded into
        ``(G, C)`` — what an engine snapshot holds.  ``hot``: the sids that
        hold a slot (a param-batched engine folds with the slot's
        reservoir; any other session folds with the shared params)."""
        for sid, ls in self.state.items():
            self._fold_acc(ls.acc, self._session_params(sid)
                           if sid in hot else self.params)
        return dict(self.state)

    def load_state(self, states: Dict[Hashable, _LearnState]) -> None:
        """Install learn states that :meth:`export_state` gave out."""
        self.state.update(states)

    def clear(self) -> None:
        self.state.clear()
        self.readouts.clear()
        self._acc_cache = None

    def readout_key(self, sid) -> Hashable:
        """The readout-pool key serving ``sid``: its tenant when one was
        given at submit, else the sid itself (private per-session pool)."""
        ls = self.state.get(sid)
        return sid if ls is None or ls.tenant is None else ls.tenant

    def pool_entry(self, sid):
        """The pool readout serving ``sid``, or None (base readout)."""
        return self.readouts.get(self.readout_key(sid))

    def dirty_sids(self) -> List[Hashable]:
        return [s for s, ls in self.state.items() if ls.dirty]

    # --------------------------------------------------- pairing bookkeeping
    def note_steps(self, sids) -> None:
        """One teacher-forcible decode step elapsed for ``sids`` — the
        pairing counter observe() accumulation keys on (a pair forms only
        when exactly one step separates consecutive teacher events)."""
        if not self.state:
            return
        for sid in sids:
            ls = self.state.get(sid)
            if ls is not None:
                ls.steps_since_fb += 1

    def note_freerun(self, sids, n: int) -> None:
        """Free-running tokens break the teacher pairing: the next observe
        of these sessions must not form a training pair (``steps_since_fb``
        overshoots 1), and grown members — which do NOT free-run — fall out
        of state sync and re-washout before accumulating again."""
        if not self.state:
            return
        for sid in sids:
            ls = self.state.get(sid)
            if ls is None:
                continue
            ls.steps_since_fb += n
            for mb in ls.members:
                mb.steps_since_fb += n
                mb.acc.skip_left = max(mb.acc.skip_left,
                                       self._growth_washout)

    def on_prompt_done(self, sid, y_teacher_last) -> None:
        """The prompt is the washout: the final teacher row re-arms the
        (state, feedback, truth) pairing so the very next decode_step +
        observe forms a training row — exactly the row offline
        fit(washout=T_prompt) keeps first.  Grown members do not ride
        prefill waves; they resynchronize off the teacher stream (echo
        state property) and re-washout before accumulating."""
        ls = self.state.get(sid)
        if ls is None:
            return
        ls.steps_since_fb = 0
        if self.cfg.use_feedback and y_teacher_last is not None:
            ls.last_fb = np.asarray(y_teacher_last, self._dtype)
        for mb in ls.members:
            mb.steps_since_fb = 0
            mb.acc.skip_left = max(mb.acc.skip_left, self._growth_washout)
            if ls.last_fb is not None:
                mb.y_fb = jnp.asarray(ls.last_fb, self._dtype)

    def cache_post_step(self, arena) -> None:
        """ONE batched D2H snapshot of the post-step arena for the
        observe() accumulation that typically follows — per-session row
        pulls there would cost two blocking transfers per sid per token
        (~20% serve overhead measured); keyed on the states array's
        identity so any other wave invalidates it."""
        if not self.state:
            return
        self._acc_cache = (arena.states,
                           np.asarray(arena.states, self._dtype),
                           np.asarray(arena.y_prev, self._dtype))

    def on_observe(self, sid, slot: int, y, arena) -> None:
        """The observe() accumulation: closes a (state, feedback, truth)
        training row IF exactly one decode step separates it from the
        previous teacher event — the state/feedback the arena holds right
        now are then exactly the feature row the offline teacher-forced
        fit would build for this position ("the prompt is the washout"
        parity).  The pre-observe ``y_prev`` is the model's prediction for
        this very token: it feeds the held-out prequential drift EWMA
        before the ground truth overwrites it."""
        ls = self.state.get(sid) if self.enabled else None
        if ls is None:
            return
        y_np = np.asarray(y, self._dtype)
        if ls.steps_since_fb == 1 and (not self.cfg.use_feedback
                                       or ls.last_fb is not None):
            cache = self._acc_cache
            if cache is not None and cache[0] is arena.states:
                # decode_step's batched snapshot: zero extra transfers
                # (and the y_prev row is the PRE-observe prediction even
                # when an earlier observe this step rewrote the arena).
                h_row, pred = cache[1][slot], cache[2][slot]
            else:
                h_row = arena.states[slot]
                pred = arena.y_prev[slot]
            if self._acc_pair(ls.acc, h_row, ls.last_fb, y_np, pred):
                ls.dirty = True
            for mb in ls.members:
                if mb.steps_since_fb == 1:
                    if self._acc_pair(
                            mb.acc, mb.h, mb.y_fb, y_np,
                            mb.pred_last if mb.w is not None else None):
                        ls.dirty = True
        for mb in ls.members:
            # Teacher forcing resynchronizes every member's feedback
            # channel regardless of pairing (echo state property pulls
            # their states back onto the teacher trajectory).
            mb.y_fb = jnp.asarray(y, self._dtype)
            mb.steps_since_fb = 0
        ls.last_fb = y_np
        ls.steps_since_fb = 0

    def _acc_pair(self, acc: _GramAcc, h, fb, y_np, pred) -> bool:
        """Buffer one (state, feedback, truth) training row — host copies,
        taken HERE because the decode wave that produced them has already
        materialized (``decode_step`` blocks on its output), so the copy is
        a cheap D2H of one row; buffering the lazy device slices instead
        turns the later fold into hundreds of tiny dispatches (measured
        ~40ms/wave vs ~1ms).  Also keeps the pre-observe prediction for the
        held-out drift EWMA.  Returns whether a training row was kept
        (washout rows only feed drift)."""
        if pred is not None:
            acc.buf_pred.append((np.asarray(pred, self._dtype), y_np))
        if acc.skip_left > 0:
            acc.skip_left -= 1
            return False
        acc.buf_h.append(np.asarray(h, self._dtype))
        acc.buf_fb.append(None if fb is None
                          else np.asarray(fb, self._dtype))
        acc.buf_y.append(y_np)
        return True

    # ---------------------------------------------------------------- folds
    def _fold_grouped(self, sids) -> None:
        """Batch the session folds of one refit wave: sessions sharing the
        engine params, one window length, and one prior-stats shape fold in
        ONE vmapped :func:`_fold_rows_batch` dispatch — at the steady serve
        cadence (every session observes every token, refits on one clock)
        that is ALL of them, and the per-wave fold cost stops scaling with
        the session count.  Stragglers (odd window lengths, first-ever
        folds mixed with decayed ones) fall through to the per-session
        :meth:`_fold_acc` untouched."""
        lam = self._refit_decay
        use_fb = self.cfg.use_feedback
        groups: Dict[tuple, list] = {}
        for sid in sids:
            acc = self.state[sid].acc
            m = len(acc.buf_h)
            if not m or (use_fb and any(f is None for f in acc.buf_fb)):
                continue
            groups.setdefault((m, acc.gram is None), []).append(acc)
        for (m, fresh), accs in groups.items():
            if len(accs) < 2:
                continue              # a lone fold gains nothing from vmap
            h = jnp.asarray(np.stack([np.stack(a.buf_h) for a in accs]),
                            self._dtype)
            y = jnp.asarray(np.stack([np.stack(a.buf_y) for a in accs]),
                            self._dtype)
            fb = (jnp.asarray(np.stack([np.stack(a.buf_fb) for a in accs]),
                              self._dtype) if use_fb else None)
            g0 = c0 = None
            if not fresh:
                g0 = jnp.stack([a.gram for a in accs])
                c0 = jnp.stack([a.cg for a in accs])
            g, c = _fold_rows_batch(self.params, h, fb, y, g0, c0, lam)
            for i, acc in enumerate(accs):
                acc.gram, acc.cg = g[i], c[i]
                acc.pairs += m
                acc.buf_h.clear()
                acc.buf_fb.clear()
                acc.buf_y.clear()

    def _fold_acc(self, acc: _GramAcc, params) -> None:
        """Fold the buffered rows into the running ``(G, C)`` — λ-decayed:
        row i of an m-row window scales by λ^((m-1-i)/2) before
        ``gram_streaming`` so BOTH G and C carry λ^(m-1-i), and the
        previously folded stats decay by λ^m (exactly the weights one
        decayed offline fit over the whole stream would use).  Also folds
        the buffered predictions into the drift EWMA.  Buffers are host
        rows (see :meth:`_acc_pair`), so the fold is ONE H2D upload plus
        the fused :func:`_fold_rows` kernel."""
        m = len(acc.buf_h)
        lam = self._refit_decay
        if m:
            h = jnp.asarray(np.stack(acc.buf_h), self._dtype)
            y = jnp.asarray(np.stack(acc.buf_y), self._dtype)
            fb = None
            if self.cfg.use_feedback:
                fb = jnp.asarray(np.stack(acc.buf_fb), self._dtype)
            acc.gram, acc.cg = _fold_rows(params, h, fb, y,
                                          acc.gram, acc.cg, lam)
            acc.pairs += m
            acc.buf_h.clear()
            acc.buf_fb.clear()
            acc.buf_y.clear()
        if acc.buf_pred:
            preds = np.stack([p for p, _ in acc.buf_pred])
            ys = np.stack([t for _, t in acc.buf_pred])
            errs = np.mean((preds - ys) ** 2, axis=1)
            acc.buf_pred.clear()
            b = self._drift_beta
            d = acc.drift
            for e in errs:
                d = float(e) if d is None else b * d + (1.0 - b) * float(e)
            acc.drift = d

    def _session_params(self, sid):
        """The param struct whose features/metric govern ``sid``'s refit —
        the slot's slice on a param-batched engine (slot i IS reservoir i,
        and batched engines never park, so the slot is always live)."""
        if not self._batched:
            return self.params
        slot = self.session_slot(sid)
        return jax.tree_util.tree_map(lambda leaf: leaf[slot], self.params)

    def _metric_of(self, params, cache_key: Hashable = None):
        """Per-row refit metric: EET blockdiag(I, QᵀQ) for diag params
        (paper Eq. 29 — refit trains directly in the eigenbasis), identity
        for standard mode (plain ridge).  The metric is a constant of the
        (frozen) params, so it caches under ``cache_key`` (slot index on a
        param-batched engine, None otherwise) — rebuilding it cost more
        than the refit solve itself."""
        m = self._metric_cache.get(cache_key)
        if m is None:
            if params.mode == "diag":
                m = esn_fn.eet_metric(params)
            else:
                m = jnp.eye(self.cfg.n_features, dtype=self._dtype)
            self._metric_cache[cache_key] = m
        return m

    # ------------------------------------------------------------- ensemble
    def _maybe_grow(self, sid, ls: _LearnState) -> None:
        """DPG ensemble growth: when the session's held-out streaming RMSE
        drifts past the threshold, sample a fresh reservoir member
        on-demand (``dpg_params`` — O(N), no diagonalization ever runs) and
        fold it into the session's ensemble.  The member starts at h=0 and
        synchronizes off the shared teacher stream (echo state property);
        it votes only after its first refit.  The drift EWMA resets so one
        excursion cannot cascade straight to ``growth_max_members``."""
        if (self._drift_threshold is None or self._batched
                or ls.acc.drift is None
                or len(ls.members) >= self._growth_max
                or ls.acc.drift ** 0.5 <= self._drift_threshold):
            return
        self._growth_seed += 1
        p = esn_fn.dpg_params(
            dataclasses.replace(self.cfg, seed=self._growth_seed),
            "noisy_golden", sigma=self._growth_sigma)
        fb0 = (jnp.zeros((self.cfg.d_out,), self._dtype)
               if ls.last_fb is None
               else jnp.asarray(ls.last_fb, self._dtype))
        mb = _Member(params=p, h=jnp.zeros((self.cfg.n,), self._dtype),
                     y_fb=fb0)
        mb.acc.skip_left = self._growth_washout
        ls.members.append(mb)
        ls.acc.drift = None
        self.tracker.log_wave({"kind": "growth", "sid": sid,
                               "members": len(ls.members)})

    def vote(self, sid, u_vec, y_primary):
        """The decode_step ensemble hook: sessions that grew DPG members
        return the validation-RMSE-weighted vote over primary + members
        (the members advance here, teacher-driven off the same input)."""
        ls = self.state.get(sid)
        if ls is None or not ls.members:
            return y_primary
        return self._step_members(ls, u_vec, y_primary)

    def _step_members(self, ls: _LearnState, u_vec, y_primary):
        """Advance the session's grown members one teacher-driven step and
        return the validation-RMSE-weighted vote over primary + members
        (weight 1/(mse+eps); members without a refit-trained readout or a
        drift estimate yet abstain)."""
        u = jnp.asarray(np.asarray(u_vec, self._dtype))[None]
        w0 = (1.0 if ls.acc.drift is None
              else 1.0 / (ls.acc.drift + 1e-6))
        votes = [(np.asarray(y_primary, np.float64), w0)]
        for mb in ls.members:
            fb_col = None
            if self.cfg.use_feedback:
                fb_col = jnp.asarray(mb.y_fb, self._dtype)[None]
            h = esn_fn.step_states(mb.params, mb.h[None],
                                   esn_fn.drive(mb.params, u, fb_col))[0]
            mb.h = h
            mb.steps_since_fb += 1
            if mb.w is None:
                continue
            x = esn_fn.assemble_features(mb.params, h[None], fb_col)
            pred = arena_mod.apply_readout(mb.w, x)[0]
            mb.pred_last = pred
            mb.y_fb = pred
            if mb.acc.drift is not None:
                votes.append((np.asarray(pred, np.float64),
                              1.0 / (mb.acc.drift + 1e-6)))
        if len(votes) == 1:
            return y_primary
        total = sum(w for _, w in votes)
        fused = sum(p * w for p, w in votes) / total
        return fused.astype(np.asarray(y_primary).dtype)

    def drift_rmse(self, sid) -> Optional[float]:
        """The session's held-out streaming RMSE estimate (sqrt of the
        prequential squared-error EWMA), folding any buffered predictions
        first.  None until at least one post-washout teacher pair landed."""
        ls = self.state.get(sid)
        if ls is None:
            return None
        self._fold_acc(ls.acc, self._session_params(sid))
        return None if ls.acc.drift is None else ls.acc.drift ** 0.5

    # ---------------------------------------------------------------- refit
    def refit_wave(self, sids, *, alpha: Optional[float] = None
                   ) -> Dict[Hashable, object]:
        """The batched refit wave: fold every target's buffers, stack the
        (G, C, metric) rows (sessions + their grown members), ONE vmapped
        generalized ridge solve, scatter the results into the readout pool
        (and — through the facade-wired ``sync_readouts`` — into the exec
        plane's device-side per-slot pool).  Timed end-to-end; under
        autotune the measurement feeds the cost model's ``c_refit(B)``
        surface, and the decode deadlines are charged either way (a refit
        wave spends real latency the decode budget must see)."""
        if not sids:
            return {}
        a = self._refit_alpha if alpha is None else float(alpha)
        t0 = time.perf_counter()
        if not self._batched:
            self._fold_grouped(sids)
        rows = []                     # (sid, member-or-None, g, c, metric)
        for sid in sids:
            ls = self.state[sid]
            p = self._session_params(sid)
            self._fold_acc(ls.acc, p)
            if ls.acc.gram is not None:
                rows.append((sid, None, ls.acc.gram, ls.acc.cg,
                             self._metric_of(
                                 p, self.session_slot(sid)
                                 if self._batched else None)))
            for mb in ls.members:
                self._fold_acc(mb.acc, mb.params)
                if mb.acc.gram is not None:
                    if mb.metric is None:
                        mb.metric = (esn_fn.eet_metric(mb.params)
                                     if mb.params.mode == "diag" else
                                     jnp.eye(self.cfg.n_features,
                                             dtype=self._dtype))
                    rows.append((sid, mb, mb.acc.gram, mb.acc.cg,
                                 mb.metric))
            self._maybe_grow(sid, ls)
            ls.dirty = False
        if not rows:
            return {}
        w = self._refit_jit(jnp.stack([r[2] for r in rows]),
                            jnp.stack([r[3] for r in rows]),
                            jnp.stack([r[4] for r in rows]), a)
        jax.block_until_ready(w)
        us = (time.perf_counter() - t0) * 1e6
        self.tracker.log_wave({"kind": "refit", "rows": len(rows),
                               "us": us})
        if self._autotune and self.cost_model is not None:
            self.cost_model.observe_refit(len(rows), us)
        self.charge(us)
        out: Dict[Hashable, object] = {}
        touched = set()
        for (sid, mb, *_), wi in zip(rows, w):
            if mb is None:
                self.activate_pool()
                key = self.readout_key(sid)
                self.readouts[key] = wi
                touched.add(key)
                out[sid] = wi
            else:
                mb.w = wi
        if touched:
            # one scatter for every hot session serving ANY refit key this
            # wave — per-key syncs would each pay a dispatch
            self.sync_readouts(self.hot_serving(touched))
        return out

"""ReservoirEngine — the thin facade over the serving planes.

Four planes, one-way imports (enforced by tests/test_serving_planes.py);
this module is the only thing that sees all of them:
``serve.telemetry`` (observability: ``Tracker`` seam + ``StatsAggregator``),
``serve.ingest`` (control: session table, admission, input queues,
backpressure), ``serve.exec_plane`` (data: the slot arena and every device
dispatch), ``serve.learn`` (streaming refit, drift, DPG growth).
Planes never import each other sideways or upward; cross-plane *runtime*
effects travel through callbacks this facade wires at construction.  The
facade holds the public API and the bit-exactness contract: every output
is identical to the pre-split monolith (pinned by the facade-parity suite).

Lifecycle: ``submit`` -> ``flush`` -> ``decode_step`` /
``decode_closed_loop`` / ``queue_inputs`` -> ``release``.
``submit/flush`` is the ONE admission surface.
"""
from __future__ import annotations

from typing import Dict, Hashable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.params import DiagParams, Readout, StandardParams
from . import store as store_mod
from .cost import WaveCostModel, cost_key
from .exec_plane import DecodeResult, EvictResult, ExecPlane
from .ingest import AdmissionFull, IngestPlane, SessionStats, SessionTable
from .learn import (LearnPlane, _GramAcc, _LearnState,  # noqa: F401
                    _Member)
from .scheduler import WaveScheduler
from .telemetry import (EngineStats, MultiTracker, StatsAggregator, Tracker,
                        make_tracker)

__all__ = ["SessionStats", "DecodeResult", "EvictResult", "EngineStats",
           "AdmissionFull", "ReservoirEngine"]


def _coerce_model(model, readout):
    """Accept a param struct or a ``LinearESN`` facade; normalize the readout."""
    if isinstance(model, (StandardParams, DiagParams)):
        params = model
    elif hasattr(model, "params") and isinstance(
            getattr(model, "params"), (StandardParams, DiagParams)):
        params = model.params          # LinearESN facade (deprecated entry)
        if readout is None:
            readout = model.readout
    else:
        mode = getattr(model, "mode", None)
        raise ValueError(f"unknown model mode {mode!r}")
    if readout is not None and not isinstance(readout, Readout):
        readout = Readout(jnp.asarray(readout))
    return params, readout


# Exec-plane internals historically reachable as engine attributes (tests,
# benchmarks, and snapshot restore poke them); forwarded read-only via
# __getattr__ so the facade stays thin without breaking the compat surface.
# Restore only ever *mutates* these containers (``eng._decode_buf[sid] =``),
# never rebinds the attribute, so read-only forwarding is enough.
_EXEC_FWD = frozenset({
    "_arena_base", "_base_valid", "_base_dirty", "_donate", "_slot_w",
    "_ens_weights", "_wave_w", "_demote_wave", "_promote_wave",
    "_ensure_hot", "_make_room", "_capacity", "_demotable",
    "_inflight_admit", "_inflight_retire", "_drain_inflight",
    "_window_settled", "_pipeline_invalidate", "_pipeline_taint",
    "_inflight_dirty_slots", "_decode_wave", "_driven_wave",
    "_dispatch_decode", "_note_decode", "_run_wave", "_record_wave",
    "_note_page", "_base_readout", "_pool_readout", "_fresh_arena",
    "_decode_budget", "_decode_jit", "_closed_jit", "_driven_jit",
    "_wave_jit", "_place_jit", "_release_jit", "_gather_jit", "_active",
    "_inflight", "_decode_buf", "_decode_meta", "_chunk_outs",
    "_decode_k_auto", "pipeline_depth",
})

#: other live views and method delegations: facade name -> (plane, name).
#: The bound plane method carries the canonical docstring — the facade adds
#: nothing to these, so it forwards instead of wrapping.
_PLANE_FWD = {
    "sessions": ("_table", "sessions"),
    "_slots": ("_table", "slots"),
    "active_sessions": ("_table", "active"),
    "ready_sessions": ("_table", "ready"),
    "free_slots": ("_table", "free_slots"),
    "_tick": ("_table", "tick"),
    "_learn_state": ("_learn_plane", "state"),
    "_readouts": ("_learn_plane", "readouts"),
    "_promote_us": ("_agg", "promote_us"),
    "max_queued": ("_ingest", "max_queued"),
    # control plane
    "queue_inputs": ("_ingest", "queue_inputs"),
    # data plane
    "_place": ("_exec", "place"),
    "state_of": ("_exec", "state_of"),
    "decode_step": ("_exec", "decode_step"),
    "observe": ("_exec", "observe"),
    "decode_closed_loop": ("_exec", "decode_closed_loop"),
    "collect_decoded": ("_exec", "collect_decoded"),
    "_activate_pool": ("_exec", "activate_pool"),
    "_sync_slot_readouts": ("_exec", "sync_slot_readouts"),
    # learn plane
    "drift_rmse": ("_learn_plane", "drift_rmse"),
    "_refit_wave": ("_learn_plane", "refit_wave"),
    "_fold_acc": ("_learn_plane", "_fold_acc"),
    "_session_params": ("_learn_plane", "_session_params"),
    "_note_admission": ("_learn_plane", "note_admission"),
    "_readout_key": ("_learn_plane", "readout_key"),
    # telemetry plane
    "clear_decode_gaps": ("_agg", "clear_gaps"),
}


class ReservoirEngine:
    """Batched multi-session serving over an immutable reservoir param struct.

    ``model``: a ``core.params`` struct (or — deprecated — a ``LinearESN``
    facade).  ``decode_slo_us``: the engine-wide default decode deadline;
    ``submit(..., decode_slo_us=)`` overrides it per session, and
    interleaved flushes decode the most-urgent deadline first — premium
    sessions cannot be starved by default-tier traffic (pinned by test).
    ``tracker``: a ``serve.telemetry.Tracker`` or spec string (``"null"``,
    ``"jsonl:PATH"``).  ``max_queued`` bounds the admission queue
    (:meth:`submit` raises :class:`AdmissionFull` beyond it).  The engine
    **snapshots (params, readout) at construction** — build it *after*
    fitting.

    Precision: the engine serves in its params' dtype — float32 on the TPU
    (x64 off), float64 in the CPU tests.  Arena, prefill scans, fused
    decode and the readout all run in that dtype with every dot at full
    precision (``core.dispatch.device_fn``); nothing runs below it.  Fit
    the readout on the host in float64 (``core.esn.fit_host``).
    """

    def __init__(self, model, max_slots: int = 8, *,
                 readout: Optional[Readout] = None, mesh=None,
                 bucket_min: int = 16, ensemble: str = "off",
                 chunk_max: Optional[int] = None, autotune: bool = False,
                 cost_model: Optional[WaveCostModel] = None,
                 decode_slo_us: Optional[float] = None,
                 decode_wave_tokens=1,
                 pipeline_depth: int = 2,
                 park_host_rows: Optional[int] = None,
                 cold_dir: Optional[str] = None,
                 learn: bool = False,
                 refit_alpha: Optional[float] = None,
                 refit_decay: float = 1.0,
                 refit_washout: int = 0,
                 drift_threshold: Optional[float] = None,
                 drift_beta: float = 0.9,
                 growth_max_members: int = 3,
                 growth_sigma: float = 0.1,
                 growth_washout: int = 64,
                 tracker=None,
                 max_queued: Optional[int] = None,
                 _param_batch: bool = False):
        self.params, self.readout = _coerce_model(model, readout)
        self.cfg = self.params.cfg
        self._batched = bool(_param_batch)
        self.max_slots = int(max_slots)
        if self.max_slots < 1:
            raise ValueError(
                f"max_slots must be >= 1, got {self.max_slots} (an engine "
                f"with 0 slots queues every session forever)")
        if self._batched:
            b = jax.tree_util.tree_leaves(self.params)[0].shape[0]
            if self.max_slots != b:
                raise ValueError(
                    f"param batch of {b} reservoirs needs max_slots == {b}, "
                    f"got {self.max_slots} (slot i runs reservoir i)")
        if ensemble not in ("off", "mean", "weighted"):
            raise ValueError(f"ensemble must be 'off', 'mean' or 'weighted', "
                             f"got {ensemble!r}")
        if ensemble != "off" and not (self._batched and
                                      self.readout is not None):
            raise ValueError(
                f"ensemble={ensemble!r} fuses the per-reservoir predictions "
                f"of a param-batched engine — use from_param_batch with a "
                f"readout")
        self.ensemble = ensemble
        # ---- learn-while-serving knobs -----------------------------------
        self._learn = bool(learn)
        if self._learn and self.readout is None:
            raise ValueError(
                "learn=True needs a base readout — streaming refit solves "
                "per-session readouts into a pool seeded from it")
        if self._learn and ensemble != "off":
            raise ValueError(
                "learn=True is per-session teacher attribution; a fused "
                "ensemble engine serves ONE logical stream — refit the "
                "members offline and set_ensemble_weights() instead")
        if not 0.0 < float(refit_decay) <= 1.0:
            raise ValueError(f"refit_decay must be in (0, 1], "
                             f"got {refit_decay}")
        if int(refit_washout) < 0:
            raise ValueError(f"refit_washout must be >= 0, "
                             f"got {refit_washout}")
        if drift_threshold is not None and drift_threshold <= 0:
            raise ValueError(f"drift_threshold must be positive (got "
                             f"{drift_threshold}); use None to disable "
                             f"DPG ensemble growth")
        if not 0.0 <= float(drift_beta) < 1.0:
            raise ValueError(f"drift_beta must be in [0, 1), "
                             f"got {drift_beta}")
        self._refit_alpha = float(self.cfg.ridge_alpha if refit_alpha is None
                                  else refit_alpha)
        self._refit_decay = float(refit_decay)
        self._refit_washout = int(refit_washout)
        self._drift_threshold = (None if drift_threshold is None
                                 else float(drift_threshold))
        self._drift_beta = float(drift_beta)
        self._growth_max = int(growth_max_members)
        self._growth_sigma = float(growth_sigma)
        self._growth_washout = int(growth_washout)
        self._dtype = self.params.dtype
        self.mesh = mesh
        self._plan = None
        if mesh is not None:
            from ..sharding import rules as sharding_rules
            self._plan = sharding_rules.plan_arena(
                mesh, self.params, self.max_slots, batched=self._batched,
                readout=self.readout)
            self.params = jax.device_put(self.params, self._plan.params)
            if self.readout is not None:
                self.readout = Readout(
                    jax.device_put(self.readout.w_out, self._plan.readout))
        self._autotune = bool(autotune)
        if decode_slo_us is not None and decode_slo_us <= 0:
            raise ValueError(
                f"decode_slo_us must be positive (got {decode_slo_us}); "
                f"use None to disable decode-aware planning")
        # "auto" resolves K per interleaved flush from the fitted c_dec(B, K)
        # surface instead of a static constructor constant.
        decode_k_auto = decode_wave_tokens == "auto"
        if decode_k_auto:
            decode_wave_tokens = 1      # resolved per flush; 1 until fitted
        if not isinstance(decode_wave_tokens, (int, np.integer)):
            raise ValueError(
                f"decode_wave_tokens must be an int >= 1 or 'auto', "
                f"got {decode_wave_tokens!r}")
        if decode_wave_tokens < 1:
            raise ValueError(f"decode_wave_tokens must be >= 1, "
                             f"got {decode_wave_tokens}")
        decode_slo_us = (None if decode_slo_us is None
                         else float(decode_slo_us))
        # pipeline_depth waves stay in flight while the host plans the next;
        # 0 = fully synchronous (the bit-exact baseline).
        if int(pipeline_depth) < 0:
            raise ValueError(f"pipeline_depth must be >= 0, "
                             f"got {pipeline_depth}")
        pipeline_depth = int(pipeline_depth)
        # Paged session store: the arena becomes a cache of hot sessions
        # over a pinned host pool and an optional disk/fsspec cold tier.
        if cold_dir is not None and park_host_rows is None:
            raise ValueError(
                "cold_dir needs park_host_rows — the cold tier is the "
                "spill target of the host pool, not a direct demote target")
        if park_host_rows is not None and self._batched:
            raise ValueError(
                "param-batched engine: slot i IS reservoir i, so a parked "
                "session cannot be promoted into whichever slot is free — "
                "paging is unsupported (park/re-admit via release + "
                "submit(sid, h0=..., slot=...) instead)")
        self._park_host_rows = (None if park_host_rows is None
                                else int(park_host_rows))
        self._cold_dir = cold_dir
        store = None
        if self._park_host_rows is not None:
            # A synchronous engine (pipeline_depth=0) gets a synchronous
            # store: no async spill/prefetch lane, so the baseline really is
            # the old serialized flush end to end.
            store = store_mod.SessionStore(
                self.cfg.n, self.cfg.d_out, self._dtype,
                host_rows=self._park_host_rows, cold_dir=cold_dir,
                io_workers=2 if pipeline_depth > 0 else 0)
        # Decode-aware planning needs a cost surface; engine-created models
        # are keyed by (backend, n, d_out) so persisted observations never
        # mis-price a different machine or model size.
        if cost_model is None and (autotune or decode_slo_us is not None
                                   or decode_k_auto or self._learn
                                   or store is not None):
            cost_model = WaveCostModel(key=cost_key(
                jax.default_backend(), self.cfg.n, self.cfg.d_out))
        # Observability: the aggregator is always first in the fan-out, so
        # stats() counters and a user trace derive from the SAME events.
        self._agg = StatsAggregator()
        if isinstance(tracker, Tracker):
            user: Optional[Tracker] = tracker
        elif tracker is not None:
            user = make_tracker(tracker)
        else:
            user = None
        self.tracker: Tracker = (MultiTracker([self._agg, user])
                                 if user is not None else self._agg)
        # ---- planes ------------------------------------------------------
        sched = WaveScheduler(bucket_min=bucket_min, chunk_max=chunk_max,
                              cost_model=cost_model)
        self._table = SessionTable(self.max_slots)
        self._exec = ExecPlane(
            self.params, self.readout, self.cfg, self._dtype,
            batched=self._batched, ensemble=self.ensemble,
            max_slots=self.max_slots, plan=self._plan,
            pipeline_depth=pipeline_depth, decode_slo_us=decode_slo_us,
            decode_wave_tokens=int(decode_wave_tokens),
            decode_k_auto=decode_k_auto, store=store, cost_model=cost_model,
            autotune=self._autotune, tracker=self.tracker,
            table=self._table, scheduler=sched)
        self._ingest = IngestPlane(
            self.cfg, self._dtype, batched=self._batched,
            max_slots=self.max_slots, table=self._table, scheduler=sched,
            default_decode_slo_us=decode_slo_us, max_queued=max_queued)
        self._learn_plane = LearnPlane(
            self.params, self.cfg, self._dtype, batched=self._batched,
            enabled=self._learn, tracker=self.tracker,
            refit_alpha=self._refit_alpha, refit_decay=self._refit_decay,
            refit_washout=self._refit_washout,
            drift_threshold=self._drift_threshold,
            drift_beta=self._drift_beta, growth_max=self._growth_max,
            growth_sigma=self._growth_sigma,
            growth_washout=self._growth_washout,
            cost_model=cost_model, autotune=self._autotune)
        self._wire_planes()

    def _wire_planes(self) -> None:
        """Cross-plane runtime effects travel through these callbacks so
        imports stay one-way; the closures read live facade state."""
        ex, ig, ln = self._exec, self._ingest, self._learn_plane
        # exec -> learn (teacher pairing, voting, refit) and -> ingest
        # (open-loop input queues).
        ex.note_admission = ln.note_admission
        ex.on_prompt_done = ln.on_prompt_done
        ex.note_freerun = ln.note_freerun
        ex.note_steps = ln.note_steps
        ex.cache_post_step = ln.cache_post_step
        ex.vote = ln.vote
        ex.on_observe = ln.on_observe
        ex.pool_entry = ln.pool_entry
        ex.learn_active = lambda: self._learn
        ex.dirty_sids = ln.dirty_sids
        ex.refit_wave = ln.refit_wave
        ex.input_depth = ig.input_depth
        ex.pop_inputs = ig.pop_inputs

        def _forget(sid):
            # One release hook: the learn state leaves with the session and
            # any still-queued open-loop inputs are dropped.
            ln.pop(sid)
            ig.drop_inputs(sid)
        ex.pop_learn = _forget
        # ingest -> exec (the one device effect admission needs: a pinned
        # placement) and -> learn (session learn-state creation).
        ig.place = ex.place
        ig.note_admission = ln.note_admission
        ig.in_store = lambda sid: (ex.store is not None and sid in ex.store)
        # learn -> exec (refit results scatter into the device pool) and ->
        # the session table / scheduler (slot resolve, wave-cost charge).
        ln.session_slot = lambda sid: self._table.sessions[sid].slot
        ln.activate_pool = ex.activate_pool
        ln.sync_readouts = ex.sync_slot_readouts
        ln.hot_serving = lambda keys: [
            (sid, st.slot) for sid, st in self._table.sessions.items()
            if ln.readout_key(sid) in keys]
        # Through the property: reset() swaps the scheduler instance.
        ln.charge = lambda us: self.scheduler.charge_decode_cost(us)

    @classmethod
    def from_param_batch(cls, params, readout: Optional[Readout] = None, *,
                         ensemble: str = "off", mesh=None,
                         bucket_min: int = 16,
                         chunk_max: Optional[int] = None,
                         autotune: bool = False,
                         cost_model: Optional[WaveCostModel] = None,
                         decode_slo_us: Optional[float] = None,
                         decode_wave_tokens=1,
                         pipeline_depth: int = 2,
                         park_host_rows: Optional[int] = None,
                         cold_dir: Optional[str] = None,
                         tracker=None,
                         max_queued: Optional[int] = None
                         ) -> "ReservoirEngine":
        """Engine over a *batch* of independently-seeded reservoirs: slot
        ``i`` is permanently bound to reservoir ``i``; one vmap-over-params
        decode trace advances all of them per token.  ``ensemble="mean"``
        averages the B predictions into ONE output per step (B cheap
        reservoirs vote on one stream)."""
        b = jax.tree_util.tree_leaves(params)[0].shape[0]
        return cls(params, max_slots=b, readout=readout, ensemble=ensemble,
                   mesh=mesh, bucket_min=bucket_min, chunk_max=chunk_max,
                   autotune=autotune, cost_model=cost_model,
                   decode_slo_us=decode_slo_us,
                   decode_wave_tokens=decode_wave_tokens,
                   pipeline_depth=pipeline_depth,
                   park_host_rows=park_host_rows, cold_dir=cold_dir,
                   tracker=tracker, max_queued=max_queued, _param_batch=True)

    # ------------------------------------------------- plane state (compat)
    # The facade owns NO serving state: every attribute below is a live
    # view into the plane that does.  Assignments propagate where the old
    # monolith allowed them (snapshot restore, tests).
    def __getattr__(self, name):
        if name in _EXEC_FWD:
            return getattr(object.__getattribute__(self, "_exec"), name)
        fwd = _PLANE_FWD.get(name)
        if fwd is not None:
            return getattr(object.__getattribute__(self, fwd[0]), fwd[1])
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    @property
    def arena(self):
        """The slot arena, its ``active`` mirror equal to the slot table
        (``ExecPlane.synced_arena``)."""
        return self._exec.synced_arena()

    @arena.setter
    def arena(self, value):
        self._exec.arena = value

    @property
    def _use_clock(self) -> int:
        return self._table.use_clock

    @_use_clock.setter
    def _use_clock(self, value: int) -> None:
        self._table.use_clock = int(value)

    @property
    def scheduler(self) -> WaveScheduler:
        return self._exec.scheduler

    @scheduler.setter
    def scheduler(self, sched: WaveScheduler) -> None:
        self._exec.scheduler = sched
        self._ingest.scheduler = sched

    @property
    def store(self):
        return self._exec.store

    @store.setter
    def store(self, value) -> None:
        self._exec.store = value

    @property
    def cost_model(self):
        return self._exec.cost_model

    @cost_model.setter
    def cost_model(self, model) -> None:
        self._exec.cost_model = model
        self._learn_plane.cost_model = model
        self.scheduler.cost_model = model

    @property
    def decode_slo_us(self):
        return self._exec.decode_slo_us

    @decode_slo_us.setter
    def decode_slo_us(self, value) -> None:
        self._exec.decode_slo_us = value
        self._ingest.default_decode_slo_us = value

    @property
    def decode_wave_tokens(self) -> int:
        return self._exec.decode_wave_tokens

    @decode_wave_tokens.setter
    def decode_wave_tokens(self, value: int) -> None:
        self._exec.decode_wave_tokens = int(value)

    # -------------------------------------------------------------- compat
    @property
    def w_out(self):
        return None if self.readout is None else self.readout.w_out

    @property
    def param_batched(self) -> bool:
        return self._batched

    # Read-only arena views — deliberately NO setters: writers go through
    # the exec plane's pure ``serve.arena`` functions, so a stray attribute
    # write (the old silent-no-op teacher-forcing bug) now raises.
    @property
    def states(self):
        return self._exec.arena.states

    @property
    def y_prev(self):
        return self._exec.arena.y_prev

    @property
    def pending(self):
        """The scheduler's queue (len/iter-able) — sessions awaiting a slot."""
        return self.scheduler

    @property
    def parked_sessions(self) -> List[Hashable]:
        """Sessions parked in the store tiers (host pool or cold records) —
        decodable via transparent promotion, invisible to
        :attr:`active_sessions` / :attr:`ready_sessions` (those are the hot
        set)."""
        return [] if self.store is None else self.store.sids

    # -------------------------------------------------- per-tenant readouts
    def _sync_key(self, key) -> None:
        """Re-scatter every hot session serving ``key`` (tenant refit: all
        the tenant's hot sessions switch together)."""
        self._exec.sync_slot_readouts(
            [(sid, st.slot) for sid, st in self.sessions.items()
             if self._readout_key(sid) == key])

    def set_readout(self, key: Hashable, w_out) -> None:
        """Install/replace the pool readout for ``key`` (a tenant, or a sid
        for a private per-session readout).  Hot sessions serving that key
        switch on their next wave; sessions admitted later gather it at
        placement.  Accepts a ``Readout`` or a bare (F, D_out) array."""
        w = jnp.asarray(getattr(w_out, "w_out", w_out), self._dtype)
        want = (self.cfg.n_features, self.cfg.d_out)
        if w.shape != want:
            raise ValueError(f"pool readout for {key!r} must be {want}, "
                             f"got {tuple(w.shape)}")
        self._exec.activate_pool()
        self._readouts[key] = w
        self._sync_key(key)

    def readout_for(self, sid):
        """The effective (F, D_out) readout currently serving ``sid`` —
        its tenant/session pool entry when one exists, else the base."""
        w = self._learn_plane.pool_entry(sid)
        if w is not None:
            return w
        if not self._batched:
            return self.w_out
        return self._exec._base_readout(self.sessions[sid].slot)

    def set_ensemble_weights(self, weights) -> None:
        """Per-reservoir voting weights for ``ensemble='weighted'`` —
        typically ``1 / (rmse_i**2 + eps)`` from each member's held-out
        RMSE.  ``None`` restores uniform voting (= the plain mean)."""
        if self.ensemble != "weighted":
            raise ValueError(
                f"set_ensemble_weights needs ensemble='weighted' "
                f"(engine has ensemble={self.ensemble!r})")
        if weights is None:
            self._exec._ens_weights = None
            return
        w = jnp.asarray(weights, self._dtype).reshape(self.max_slots)
        self._exec._ens_weights = w

    # ------------------------------------------------------------- lifecycle
    def submit(self, sid: Hashable, u=None, y_teacher=None, *, h0=None,
               y0=None, slot: Optional[int] = None,
               tenant: Optional[Hashable] = None,
               decode_slo_us: Optional[float] = None) -> Optional[int]:
        """Queue ``sid`` for wave-batched admission — the ONE admission
        surface (:meth:`flush` drains the queue).  ``slot=`` pins a
        placement, ``tenant=`` keys the readout pool, ``decode_slo_us=``
        overrides the engine-wide decode deadline for this session.  At
        ``max_queued`` capacity raises :class:`AdmissionFull` (the front
        end's backpressure).  See ``serve.ingest.IngestPlane.submit``."""
        return self._ingest.submit(sid, u, y_teacher, h0=h0, y0=y0,
                                   slot=slot, tenant=tenant,
                                   decode_slo_us=decode_slo_us)

    def flush(self, *, method: str = "auto", chunk: int = 128,
              want_outputs: bool = False,
              max_waves: Optional[int] = None,
              decode_interleave: bool = False,
              decode_sids=None, refit: bool = False
              ) -> Dict[Hashable, object]:
        """Drain the admission queue, one batched prefill per same-bucket
        wave; returns sid -> per-step outputs for prompts *completed* this
        flush.  ``decode_interleave=True`` (needs ``decode_slo_us`` —
        engine-wide, or per-session deadlines covering an explicit
        ``decode_sids`` set) alternates SLO-protected decode waves with
        prefill: tighter (premium) deadlines decode first, and due sessions
        with rows buffered via :meth:`queue_inputs` advance teacher-driven
        instead of free-running.  Planning only reorders waves, so every
        output is bit-exact vs the decode-blind schedule.  ``refit=True``
        (needs ``learn=True``) batch-refits dirty sessions after the
        drain.  Full contract: ``serve.exec_plane.ExecPlane.flush``."""
        if refit and not self._learn:
            raise ValueError("flush(refit=True) needs learn=True on the "
                             "engine — nothing accumulates (G, C) otherwise")
        return self._exec.flush(method=method, chunk=chunk,
                                want_outputs=want_outputs,
                                max_waves=max_waves,
                                decode_interleave=decode_interleave,
                                decode_sids=decode_sids, refit=refit)

    # ------------------------------------------------- learn-while-serving
    def refit(self, sid: Optional[Hashable] = None, *,
              alpha: Optional[float] = None) -> Dict[Hashable, object]:
        """Solve fresh readouts from the streaming ``(G, C)`` — one batched
        device wave over every dirty session (or just ``sid``).  The
        solved readout lands in the session's tenant pool entry (hot slots
        re-scatter immediately) and is returned per sid; matches offline
        ``core.esn.fit`` on the concatenated teacher stream ≤1e-5 ("the
        prompt is the washout", pinned by test)."""
        if not self._learn:
            raise ValueError("refit needs learn=True on the engine — "
                             "nothing accumulates (G, C) otherwise")
        if sid is None:
            sids = self._learn_plane.dirty_sids()
        else:
            if sid not in self._learn_plane.state:
                raise KeyError(f"session {sid!r} has no learn state (was it "
                               f"admitted with learn=True on the engine?)")
            sids = [sid]
        return self._learn_plane.refit_wave(sids, alpha=alpha)

    # ---------------------------------------------------------------- stats
    def stats(self) -> EngineStats:
        """Engine-lifetime serving counters (cumulative across ``reset``)
        as a typed frozen :class:`EngineStats` — attribute access or
        ``.to_dict()``; dict-style key access is REMOVED (see the README
        migration table).  Counters derive from the same event stream a
        ``tracker=`` sink records, merged with per-plane occupancy
        snapshots; field docs live on
        :class:`~repro.serve.telemetry.EngineStats`."""
        d = self._agg.snapshot()
        if self.cost_model is not None:
            wave_costs = self.cost_model.records()
        else:           # no model: best effort from the (bounded) wave log
            wave_costs = [{"b": w["rows"], "t_bucket": w["t_bucket"],
                           "us": w["us"]}
                          for w in d["wave_log"]
                          if w["us"] is not None and w["rows"] > 0]
        d.update(
            sessions_active=len(self.sessions),
            sessions_ready=len(self.ready_sessions),
            sessions_queued=len(self.scheduler),
            sessions_parked=(0 if self.store is None else len(self.store)),
            store=None if self.store is None else self.store.stats(),
            chunks_in_flight=sum(st.prefill_pending
                                 for st in self.sessions.values()),
            pipeline_depth=self.pipeline_depth,
            pipeline_inflight=len(self._exec._inflight),
            sessions_dirty=sum(ls.dirty
                               for ls in self._learn_plane.state.values()),
            wave_costs=wave_costs,
        )
        return EngineStats(**d)

    # ------------------------------------------------------------ lifecycle
    def release(self, sid: Hashable, *, drop: bool = False):
        """Hand ``sid``'s state back and forget the session — the ONE
        release surface.  Returns an :class:`EvictResult` (unpacks as the
        historical ``(state, y_prev)`` 2-tuple; ``.decoded`` carries any
        uncollected tokens).  ``drop=True`` discards the state.  Learn
        state, the per-request deadline, and queued open-loop inputs leave
        with the session; the tenant's pooled readout stays.  Full
        contract: ``serve.exec_plane.ExecPlane.release``."""
        return self._exec.release(sid, drop=drop)

    def evict(self, sid: Hashable):
        """Deprecated alias for :meth:`release` (kept one release for
        migration — see the README migration table)."""
        return self.release(sid)

    def reset(self):
        """Drop all sessions (active + queued) and zero the state arena.
        Keeps the compiled step functions, the learned cost model, and the
        cumulative :meth:`stats` counters — cheap way to reuse an engine."""
        self._exec.reset()
        self._learn_plane.clear()
        self._ingest.clear()
        self._agg.promote_us.clear()
        old = self.scheduler
        self.scheduler = WaveScheduler(bucket_min=old.bucket_min,
                                       max_wave=old.max_wave,
                                       chunk_max=old.chunk_max,
                                       cost_model=old.cost_model)

    # ----------------------------------------------------- snapshot/restore
    def snapshot(self, path: str) -> str:
        """Serialize the whole serving process to ``path`` — everything
        :meth:`restore` needs to resume mid-workload bit-exactly.  Atomic
        (tmp-rename + ``_COMPLETE`` marker).  See
        ``serve.store.snapshot_engine``."""
        return store_mod.snapshot_engine(self, path)

    @classmethod
    def restore(cls, path: str, *, mesh=None) -> "ReservoirEngine":
        """Rebuild an engine from :meth:`snapshot` output and resume
        serving bit-exactly (pinned by test).  ``mesh`` re-places the
        arena on a new device mesh.  Stats counters start fresh."""
        return store_mod.restore_engine(cls, path, mesh=mesh)

    # Decode (``decode_step`` / ``observe`` / ``decode_closed_loop`` /
    # ``collect_decoded``), ``queue_inputs``, ``state_of``, ``drift_rmse``
    # and ``clear_decode_gaps`` forward straight to their owning plane via
    # ``_PLANE_FWD`` — the bound plane method carries the contract.

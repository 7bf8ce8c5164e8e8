"""Exec (data) plane — wave dispatch against the slot arena, the pipelined
in-flight window, tiered paging waves, and SLO-interleaved decode
(free-running and teacher-driven).

Everything that touches the device lives here: the jitted prefill /
decode / place / page-out / gather dispatches, the per-slot readout pool's
device side, the decode output buffers, and the flush drain loop that
turns the scheduler's planned waves into dispatches.

Layering: imports only ``core``, ``serve.arena`` / ``serve.store`` /
``serve.scheduler`` / ``serve.cost`` — never the ingest or learn planes
and never the engine facade (enforced by tests/test_serving_planes.py).
Control-plane state (session table, admission queue) and learn-plane
effects (pairing counters, Gram snapshots, ensemble voting) reach this
plane only through callbacks the facade wires at construction; every
counter it used to bump in place is now an event emitted through the
telemetry plane's ``Tracker`` seam.  Host time is marked by the
telemetry plane's spans: ``serve.plan`` (the scheduler picks a wave),
``serve.wave`` (one prefill wave), ``serve.dispatch`` (each jitted call on
the serving path, attribute ``program``) and ``serve.block`` (each host
wait on the device).
"""
from __future__ import annotations

import collections.abc
import dataclasses
import functools
import time
from typing import Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dispatch
from . import arena as arena_mod
from .scheduler import WaveItem, bucket_length
from .telemetry import span

__all__ = ["ExecPlane", "DecodeResult", "EvictResult"]


@dataclasses.dataclass(frozen=True)
class DecodeResult:
    """The one decode-output type: what :meth:`ReservoirEngine.collect_decoded`
    returns for single-step, interleaved, driven, and fused K-token decode
    alike.

    ``tokens``: sid -> (n_tokens, D_out) host numpy array — every decode path
    buffers in this shape, so a caller never branches on where a token came
    from.  A decode wave's output crosses to the host in ONE copy, started
    at dispatch and made by the first drain that needs it; each session's
    rows are numpy slices of that copy.
    ``waves``: per-dispatch metadata dicts (``kind`` "step" / "closed_loop" /
    "interleave" / "driven", ``rows``, ``tokens`` per row, ``us`` wall time
    when timed, ``fused`` whether the K-token fused kernel ran) for the
    dispatches whose tokens this result drained.  ``pulls``: the host copies
    this drain made; ``pulled_bytes``: the bytes those copies moved (each
    a whole wave's ``(K, max_slots, D_out)`` output); ``waited``: how many
    of them found the wave's output not yet computed when the drain began;
    ``unready``: the sessions with a token not yet computed then.
    Mapping-shaped on ``tokens`` (iter / ``[]`` / ``items`` / ``get``), so
    dict-era callers keep working unchanged.
    """
    tokens: Dict[Hashable, np.ndarray]
    waves: Tuple[dict, ...] = ()
    pulls: int = 0
    pulled_bytes: int = 0
    waited: int = 0
    unready: int = 0

    def __getitem__(self, sid):
        return self.tokens[sid]

    def __iter__(self):
        return iter(self.tokens)

    def __len__(self) -> int:
        return len(self.tokens)

    def __contains__(self, sid) -> bool:
        return sid in self.tokens

    def keys(self):
        return self.tokens.keys()

    def values(self):
        return self.tokens.values()

    def items(self):
        return self.tokens.items()

    def get(self, sid, default=None):
        return self.tokens.get(sid, default)


class _Wave:
    """One decode dispatch's output ``ys`` (K, max_slots, D_out) on its way
    to the host.  The device-to-host copy starts at dispatch
    (``copy_to_host_async``), so the serving loop never waits for it there;
    :meth:`host` turns it into a numpy array once, whichever of the wave's
    sessions is drained first, and every session's rows are slices of it."""
    __slots__ = ("_ys", "_host")

    def __init__(self, ys):
        ys.copy_to_host_async()
        self._ys = ys
        self._host = None

    @property
    def pulled(self) -> bool:
        return self._host is not None

    @property
    def nbytes(self) -> int:
        return (self._ys if self._host is None else self._host).nbytes

    def ready(self) -> bool:
        """Whether the output is computed (or already on the host)."""
        return self._host is not None or self._ys.is_ready()

    def host(self) -> np.ndarray:
        if self._host is None:
            with span("serve.block"):
                self._host = np.asarray(self._ys)
            self._ys = None
        return self._host


class _Rows:
    """One session's column of a decode wave, as the decode buffers hold it:
    ``np.asarray`` reads it from the wave's one host copy."""
    __slots__ = ("wave", "slot")

    def __init__(self, wave: _Wave, slot: int):
        self.wave = wave
        self.slot = slot

    def __array__(self, dtype=None, copy=None):
        rows = self.wave.host()[:, self.slot]
        if dtype is not None:
            rows = rows.astype(dtype)
        return rows.copy() if copy else rows


class _WaveTokens(collections.abc.Mapping):
    """What :meth:`ExecPlane.decode_closed_loop` returns: sid -> the wave's
    (n_steps, D_out) host rows, read from the wave's one host copy on first
    access — a caller that drains through ``collect_decoded`` alone never
    waits on the device here."""
    __slots__ = ("_rows",)

    def __init__(self, rows: Dict[Hashable, _Rows]):
        self._rows = rows

    def __getitem__(self, sid) -> np.ndarray:
        return np.asarray(self._rows[sid])

    def __iter__(self):
        return iter(self._rows)

    def __len__(self) -> int:
        return len(self._rows)


class EvictResult(tuple):
    """What :meth:`ReservoirEngine.evict` returns: unpacks as the historical
    ``(state, y_prev)`` 2-tuple (every existing ``state, y = evict(sid)``
    call site keeps working), and additionally carries ``.decoded`` — the
    :class:`DecodeResult` of any tokens the session had buffered but not yet
    collected.  Eviction used to drop that buffer silently (documented, but
    still token loss); now the tokens leave with the session."""

    def __new__(cls, state, y_prev, decoded: DecodeResult):
        self = super().__new__(cls, (state, y_prev))
        self.decoded = decoded
        return self

    @property
    def state(self):
        return self[0]

    @property
    def y_prev(self):
        return self[1]


class ExecPlane:
    """Owns the arena and every device dispatch.  ``table`` (the ingest
    plane's session table) and ``scheduler`` are facade-wired references —
    shared state, one-way imports.  The ``tracker`` receives every wave /
    page / decode / pipeline event; the facade's ``StatsAggregator``
    derives the ``stats()`` counters from that same stream."""

    def __init__(self, params, readout, cfg, dtype, *, batched: bool,
                 ensemble: str, max_slots: int, plan, pipeline_depth: int,
                 decode_slo_us: Optional[float], decode_wave_tokens: int,
                 decode_k_auto: bool, store, cost_model, autotune: bool,
                 tracker, table, scheduler):
        self.params = params
        self.readout = readout
        self.cfg = cfg
        self._dtype = dtype
        self._batched = bool(batched)
        self.ensemble = ensemble
        self.max_slots = int(max_slots)
        self._plan = plan
        self.pipeline_depth = int(pipeline_depth)
        self.decode_slo_us = decode_slo_us
        self.decode_wave_tokens = int(decode_wave_tokens)
        self.decode_k_auto = bool(decode_k_auto)
        self.store = store
        self.cost_model = cost_model
        self._autotune = bool(autotune)
        self.tracker = tracker
        self.table = table
        self.scheduler = scheduler
        self._ens_weights = None
        self._slot_w = None
        self.arena = self._fresh_arena()
        self._chunk_outs: Dict[Hashable, List] = {}
        self._decode_buf: Dict[Hashable, List] = {}
        self._decode_meta: List[dict] = []
        # Pipelined-executor window: dispatched-but-unretired waves, oldest
        # first.  Each entry carries the lazy output to block on (marker)
        # and the cost model's predicted wave cost (the window bound).
        self._inflight = collections.deque()
        # Releases since the arena's ``active`` mirror was last written from
        # the slot table: ``release`` makes no device call, and the next
        # placement (or a read through ``synced_arena``) folds them in.
        self._freed = 0
        # Every device function traces with full-precision dots (the engine
        # serves in its params' dtype, float32 on the TPU, not below it) and
        # with the arena's mesh in context (see core.dispatch.device_fn).
        def on_device(fn):
            return dispatch.device_fn(
                fn, None if plan is None else plan.mesh)
        self._decode_jit = jax.jit(on_device(functools.partial(
            arena_mod.decode_step, batched=self._batched,
            ensemble=self.ensemble)))
        # Closed-loop decode routes through the fused K-token path
        # (arena.closed_loop_fused -> core.dispatch.run_decode_fused): one
        # dispatch per wave instead of per token, Pallas kernel on TPU, jnp
        # reference elsewhere; dense params fall back to the scan inside.
        # On the TPU the closed-loop decode donates its arena argument, so
        # the (B, N) slot state updates in place instead of being copied
        # each wave; the superseded arena value is never read again.
        donate = (2,) if jax.default_backend() == "tpu" else ()
        self._closed_jit = jax.jit(
            on_device(functools.partial(arena_mod.closed_loop_fused,
                                        batched=self._batched,
                                        ensemble=self.ensemble)),
            static_argnums=4, donate_argnums=donate)
        self._driven_jit = jax.jit(
            on_device(functools.partial(arena_mod.driven_loop,
                                        batched=self._batched,
                                        ensemble=self.ensemble)))
        self._wave_jit = jax.jit(
            on_device(functools.partial(arena_mod.prefill_wave,
                                        batched=self._batched)),
            static_argnames=("method", "chunk", "want_outputs"))
        # Paging bundles as ONE executable each: eagerly, place_many /
        # release_many / gather_rows cost several device dispatches per
        # wave, and under the pipelined executor every dispatch also draws
        # down the backend's bounded in-flight-computation budget — eager
        # paging ops exhaust it mid-round and the "overlapped" host work
        # stalls on dispatch backpressure behind the in-flight scan.
        self._place_jit = jax.jit(arena_mod.place_many)
        self._release_jit = jax.jit(arena_mod.release_many)
        self._gather_jit = jax.jit(arena_mod.gather_rows)
        # ---- facade-wired cross-plane callbacks (learn / ingest) ---------
        self.note_admission = lambda sid, tenant: None
        self.on_prompt_done = lambda sid, y_last: None
        self.note_freerun = lambda sids, n: None
        self.note_steps = lambda sids: None
        self.cache_post_step = lambda arena: None
        self.vote = lambda sid, u_vec, y: y
        self.on_observe = lambda sid, slot, y, arena: None
        self.pool_entry = lambda sid: None
        self.learn_active = lambda: False
        self.pop_learn = lambda sid: None
        self.input_depth = lambda sid: 0
        self.pop_inputs = lambda sid, k: []
        self.dirty_sids = lambda: []
        self.refit_wave = lambda sids: {}

    def _fresh_arena(self) -> arena_mod.SlotArena:
        return self._on_plan(arena_mod.make_arena(
            self.cfg.n, self.cfg.d_out, self.max_slots, self._dtype))

    def _on_plan(self, ar: arena_mod.SlotArena) -> arena_mod.SlotArena:
        if self._plan is None:
            return ar
        return arena_mod.SlotArena(
            states=jax.device_put(ar.states, self._plan.arena["states"]),
            y_prev=jax.device_put(ar.y_prev, self._plan.arena["y_prev"]),
            active=jax.device_put(ar.active, self._plan.arena["active"]))

    def export_state(self) -> dict:
        """What an engine snapshot holds of this plane: the arena (its
        ``active`` mirror synced), the undrained decode buffers (sid -> row
        chunks), their per-dispatch metadata, and the buffered chunk
        outputs (sid -> output chunks)."""
        return {"arena": self.synced_arena(), "decode_buf": self._decode_buf,
                "decode_meta": self._decode_meta,
                "chunk_outs": self._chunk_outs}

    def load_state(self, arena: arena_mod.SlotArena, decode_buf,
                   decode_meta, chunk_outs) -> None:
        """Install :meth:`export_state`'s pieces, the arena placed on the
        engine's mesh."""
        self.arena = self._on_plan(arena)
        self._freed = 0
        self._decode_buf.update(decode_buf)
        self._decode_meta.extend(decode_meta)
        self._chunk_outs.update(chunk_outs)

    def synced_arena(self) -> arena_mod.SlotArena:
        """The arena with its ``active`` mirror equal to the slot table —
        what a reader outside the plane gets.  After releases that no
        placement has folded in yet, the table's occupancy is written first,
        in one transfer; the serving path never reads the mirror, so this
        never runs there."""
        if self._freed:
            self.arena = dataclasses.replace(
                self.arena, active=jax.device_put(
                    self.table.occupancy(), self.arena.active.sharding))
            self._freed = 0
        return self.arena

    @property
    def w_out(self):
        return None if self.readout is None else self.readout.w_out

    # ---------------------------------------------------------------- paging
    def _demotable(self, protect=frozenset()) -> List[Hashable]:
        """Hot sessions eligible to park, least-recently-used first: ready
        (no chunk waves in flight — a mid-prompt slot's carry is owed to the
        scheduler's queued chunks) and not protected (a flush's decode set,
        a promote wave's own targets)."""
        return self.table.demotable(protect)

    def _capacity(self, protect=frozenset()) -> int:
        """Admission capacity for the scheduler: free slots, plus — on a
        paged engine — every demotable hot session (admitting over the free
        slots parks the LRU idle sessions instead of rejecting: capacity is
        sessions, not slots)."""
        cap = self.table.free_slots
        if self.store is not None:
            cap += len(self._demotable(protect))
        return cap

    def _note_page(self, rows: int, us: float, *, promote: bool) -> None:
        """Page-wave accounting: the telemetry event (the aggregator derives
        the counters and promote-latency window from it), the cost model's
        page surface (autotune only — mirrors decode: in pipelined serving
        the blocking transfer also drains queued waves, and that drain time
        would poison the fit), and the decode deadlines (a page wave spends
        real latency the decode budget must see)."""
        self.tracker.log_wave({"kind": "page", "promote": promote,
                               "rows": rows, "us": us})
        if self._autotune and self.cost_model is not None:
            self.cost_model.observe_page(rows, us)
        self.scheduler.charge_decode_cost(us)

    # ---------------------------------------------------- pipelined executor
    def _inflight_admit(self, marker, pred_us: float) -> None:
        """Admit a freshly dispatched wave into the in-flight window, then
        retire from the front until the window is legal again: at most
        ``pipeline_depth`` waves deep, AND — when a decode SLO is set — the
        summed *predicted* cost of the in-flight waves stays under it (an
        unbounded dispatch queue is exactly how async dispatch blows a
        latency SLO: every queued wave is latency someone's next token must
        wait behind)."""
        self._inflight.append({"marker": marker, "pred_us": float(pred_us)})
        while len(self._inflight) > self.pipeline_depth or (
                self.decode_slo_us is not None and len(self._inflight) > 1
                and sum(e["pred_us"] for e in self._inflight)
                > self.decode_slo_us):
            self._inflight_retire()
        self.tracker.log_wave({"kind": "pipeline",
                               "inflight": len(self._inflight)})

    def _inflight_retire(self) -> None:
        """Block on the oldest in-flight wave.  The blocked time is the
        host's pipeline-idle time — accounted so the overlap-efficiency
        benchmark can report 1 - host_idle/wall."""
        e = self._inflight.popleft()
        t0 = time.perf_counter()
        # Under donation (TPU) a later dispatch may have consumed the
        # marker's buffer.  That dispatch read it, so it runs after this
        # wave, and the newer window entries cover it.
        if not e["marker"].is_deleted():
            with span("serve.block"):
                jax.block_until_ready(e["marker"])
        self.tracker.log_wave({"kind": "host_block",
                               "us": (time.perf_counter() - t0) * 1e6})

    def _drain_inflight(self) -> None:
        while self._inflight:
            self._inflight_retire()

    def _window_settled(self) -> None:
        """The caller just blocked on a value downstream of every in-flight
        wave (a decode wave's tokens, a promote's scatter): the whole window
        is materialized — forget it without further blocking."""
        self._inflight.clear()

    def _demote_wave(self, sids: List[Hashable]) -> None:
        """Park ``sids``: gather their slot rows from the current arena in
        ONE device->host transfer, free the slots in ONE scatter, and hand
        the rows (plus each session's accounting struct, verbatim) to the
        store.  The ``device_get`` blocks, and the gather runs after every
        in-flight wave that writes the arena."""
        if not sids:
            return
        idx = jnp.asarray([self.table.sessions[s].slot for s in sids])
        t0 = time.perf_counter()
        states, ys = jax.device_get(self._gather_jit(self.arena, idx))
        us = (time.perf_counter() - t0) * 1e6
        stats = []
        for sid in sids:
            st = self.table.sessions.pop(sid)
            self.table.slots[st.slot] = None
            st.slot = -1
            stats.append(st)
        self.arena = self._release_jit(self.arena, idx)
        self.store.park_many(sids, np.asarray(states), np.asarray(ys),
                             stats)
        self._note_page(len(sids), us, promote=False)

    def _promote_wave(self, sids: List[Hashable]) -> None:
        """Un-park ``sids`` into free slots: one store fetch (host rows or
        cold records), ONE ``place_many`` scatter.  The wave blocks until
        the states are resident — a promote is always on someone's decode
        critical path, and an unmaterialized state is still latency; the
        measured restore latency feeds ``promote_us_p95`` in ``stats()``.
        """
        if not sids:
            return
        t0 = time.perf_counter()
        states, ys, stats = self.store.fetch_many(sids)
        slots = []
        for sid, st in zip(sids, stats):
            slot = self.table.slots.index(None)
            self.table.slots[slot] = sid
            st.slot = slot
            self.table.sessions[sid] = st
            slots.append(slot)
        self.arena = self._place_jit(self.arena, jnp.asarray(slots),
                                     jnp.asarray(states), jnp.asarray(ys),
                                     self.table.occupancy())
        self._freed = 0
        # Promoted sessions re-enter on fresh slots: re-scatter their tenant
        # pool readouts so the next decode wave serves the right weights.
        self.sync_slot_readouts(list(zip(sids, slots)))
        # A promote stays blocking even in the pipelined executor: it is on
        # someone's decode critical path, and an unmaterialized state is
        # still latency — the measured restore latency must be real.  The
        # block also materializes every in-flight wave (the scatter depends
        # on them), so the window settles for free.
        jax.block_until_ready(self.arena.states)
        self._window_settled()
        us = (time.perf_counter() - t0) * 1e6
        self._note_page(len(sids), us, promote=True)

    def _ensure_hot(self, sids, protect=frozenset()) -> None:
        """Transparently promote any parked sessions in ``sids`` — called at
        the top of every decode/observe path, so decoding a parked session
        just works: the LRU idle hot sessions page out to make room.  No-op
        on an unpaged engine or when everything is already hot."""
        if self.store is None:
            return
        parked = [s for s in sids if s in self.store]
        if not parked:
            return
        # Kick the cold->host reads onto the store's async lane now: they
        # overlap the demote wave below (and any in-flight prefill), and
        # _promote_wave's fetch consumes the per-session futures — blocking
        # only if a read is genuinely still in flight when needed.
        self.store.prefetch_many(parked)
        need = len(parked) - self.table.free_slots
        if need > 0:
            victims = self._demotable(set(sids) | set(protect))[:need]
            if len(victims) < need:
                raise RuntimeError(
                    f"cannot promote {len(parked)} parked session(s): "
                    f"{self.table.free_slots} free slot(s), "
                    f"{len(victims)} demotable — decode at most "
                    f"max_slots={self.max_slots} sessions per wave")
            self._demote_wave(victims)
        self._promote_wave(parked)

    def _make_room(self, wave: List[WaveItem], protect=frozenset()) -> None:
        """Demote enough LRU idle sessions that the popped wave's fresh rows
        all find free slots (the scheduler's ``capacity`` already counted
        them, so the victims exist by construction)."""
        if self.store is None:
            return
        need = sum(it.first for it in wave) - self.table.free_slots
        if need > 0:
            self._demote_wave(self._demotable(protect)[:need])

    # -------------------------------------------- per-tenant readouts (device)
    def _wave_w(self):
        """The readout the wave functions serve: the (max_slots, F, D_out)
        per-slot pool once any tenant readout has diverged from the base,
        else the engine-wide ``w_out`` (zero pool overhead until then)."""
        return self.w_out if self._slot_w is None else self._slot_w

    def activate_pool(self) -> None:
        """Materialize the per-slot readout pool (one-time retrace of the
        wave fns: 2D -> 3D ``w_out``).  Seeded by broadcasting the base
        readout to every slot; a param-batched engine's stacked readout
        already IS the pool."""
        if self._slot_w is not None:
            return
        if self.readout is None:
            raise ValueError("per-tenant readout pools need a base readout")
        w = self.w_out
        if not self._batched:
            w = jnp.broadcast_to(w, (self.max_slots,) + w.shape)
        self._slot_w = jnp.asarray(w)

    def _base_readout(self, slot: int):
        return (None if self.readout is None
                else self.w_out[slot] if self._batched else self.w_out)

    def _pool_readout(self, sid, slot: int):
        w = self.pool_entry(sid)
        return self._base_readout(slot) if w is None else w

    def sync_slot_readouts(self, pairs) -> None:
        """Scatter each (sid, slot) pair's effective readout into the device
        pool — called at every placement/promotion.  No-op while the pool is
        dormant (every slot serves the base readout by construction)."""
        if self._slot_w is None:
            return
        pairs = list(pairs)
        if not pairs:
            return
        idx = jnp.asarray([slot for _, slot in pairs])
        ws = jnp.stack([self._pool_readout(sid, slot)
                        for sid, slot in pairs])
        self._slot_w = self._slot_w.at[idx].set(ws)

    # ------------------------------------------------------------------ flush
    def flush(self, *, method: str = "auto", chunk: int = 128,
              want_outputs: bool = False,
              max_waves: Optional[int] = None,
              decode_interleave: bool = False,
              decode_sids=None, refit: bool = False
              ) -> Dict[Hashable, object]:
        """The drain loop behind ``ReservoirEngine.flush`` (see the facade
        docstring for the full contract).  Planning only reorders waves, so
        every output is bit-exact vs the decode-blind schedule."""
        if not decode_interleave:
            decode_sids = []
        else:
            if self.decode_slo_us is None:
                # Per-session SLOs (submit(decode_slo_us=...)) can license
                # the flush without an engine-wide default — but only for an
                # explicit, fully-tracked protected set.
                if (decode_sids is None or not decode_sids
                        or any(self.scheduler.decode_slo_of(s) is None
                               for s in decode_sids)):
                    raise ValueError(
                        "decode_interleave=True needs decode_slo_us set on "
                        "the engine — the latency budget that prices when a "
                        "decode wave must preempt prefill")
            driven_ok = (decode_sids is not None and decode_sids
                         and all(self.input_depth(s) > 0
                                 for s in decode_sids))
            if self.readout is None or (self.cfg.d_in != self.cfg.d_out
                                        and not driven_ok):
                raise ValueError(
                    "interleaved decode waves free-run (closed loop): the "
                    "engine needs a trained readout and d_in == d_out")
            if decode_sids is not None:
                decode_sids = list(dict.fromkeys(decode_sids))
                # Paged engine: a parked decoder is still a valid protected
                # decoder — promote it now so the ready check below sees it.
                self._ensure_hot(decode_sids)
            ready = self.table.ready
            if decode_sids is None:
                decode_sids = list(ready)
            else:
                missing = [s for s in decode_sids if s not in set(ready)]
                if missing:
                    raise KeyError(
                        f"decode_sids must be ready sessions; not ready: "
                        f"{missing!r}")
            # Per-request decode deadlines live in the scheduler; sessions
            # that predate SLO serving (restored snapshots) inherit the
            # engine-wide default here, so the budget math below always has
            # an entry per protected decoder.
            if self.decode_slo_us is not None:
                for s in decode_sids:
                    if self.scheduler.decode_slo_of(s) is None:
                        self.scheduler.track_decode(s, self.decode_slo_us)
            if self.decode_k_auto and self.cost_model is not None:
                # K-adaptive wave sizing: resolve decode_wave_tokens for
                # this flush from the fitted c_dec(B, K) surface — largest
                # K whose marginal cost/token still improves, capped so the
                # whole wave fits the tightest decode SLO in the set.
                slo = self.decode_slo_us
                if decode_sids:
                    slo = min(self.scheduler.decode_slo_of(s)
                              for s in decode_sids)
                self.decode_wave_tokens = self.cost_model.best_decode_k(
                    max(1, len(decode_sids)), slo_us=slo)
        results: Dict[Hashable, object] = {}
        protect = frozenset(decode_sids)
        waves_run = 0
        just_decoded = False
        while max_waves is None or waves_run < max_waves:
            # Paged engine: capacity counts demotable hot sessions too — a
            # full arena admits by parking its LRU idle sessions, so the
            # queue drains as long as *sessions* fit, not slots.  The true
            # free-slot count still goes to the scheduler so the budget fit
            # can price the forced demote page wave (c_page of the
            # overflow) against the same decode SLO.
            with span("serve.plan"):
                capacity = self._capacity(protect)
                free = (self.table.free_slots if self.store is not None
                        else None)
                if not self.scheduler.has_runnable(capacity):
                    break
                budget = (self._decode_budget(decode_sids)
                          if decode_sids else None)
                wave = self.scheduler.next_wave(capacity, budget_us=budget,
                                                free_slots=free)
            if not wave:
                if not just_decoded:
                    # Runnable prefill exists but is over the decode budget:
                    # a decode wave runs instead and resets the clock.  It
                    # does NOT count toward max_waves — a partial drain's
                    # wave quota is prefill progress, and spending it on
                    # decode would livelock a flush(max_waves=1) loop under
                    # an unsatisfiable SLO (pinned by test).
                    self._decode_due(decode_sids)
                    just_decoded = True
                    continue
                # Fresh budget: waive the shrink-efficiency floor — a
                # slow-but-SLO-compliant part-wave beats blowing the budget
                # on the full one.
                with span("serve.plan"):
                    wave = self.scheduler.next_wave(
                        capacity, budget_us=self._decode_budget(decode_sids),
                        shrink_floor=0.0, free_slots=free)
                    if not wave:
                        # Truly unsatisfiable: not even one row fits the
                        # SLO; run unbudgeted rather than spin decode-only
                        # forever.
                        wave = self.scheduler.next_wave(capacity,
                                                        free_slots=free)
                if not wave:
                    break
            just_decoded = False
            waves_run += 1
            self._make_room(wave, protect)
            with span("serve.wave", rows=len(wave),
                      t_bucket=lambda: self._bucket(wave),
                      sids=lambda: [it.sid for it in wave if it.first]):
                self._run_wave(wave, capacity, results, method=method,
                               chunk=chunk, want_outputs=want_outputs)
            if (self.pipeline_depth > 0 and not self._autotune
                    and self.store is not None):
                # Plan one wave ahead against *predicted* post-wave
                # occupancy (pure host bookkeeping — the slot table is
                # already updated at dispatch time) and run the planned
                # wave's page-out now.  The next iteration's next_wave pops
                # exactly this wave (peek is exact), and _make_room then
                # finds the slots already free.
                with span("serve.plan"):
                    planned = self.scheduler.peek_wave(
                        self._capacity(protect))
                if planned:
                    self._make_room(planned, protect)
        if refit:
            dirty = self.dirty_sids()
            if dirty and decode_sids and self.cost_model is not None:
                b = self._decode_budget(decode_sids)
                if (b is not None and
                        self.cost_model.predict_refit_us(len(dirty)) > b):
                    # The refit wave would blow the decode budget: decode
                    # first (fresh budget), then solve.
                    self._decode_due(decode_sids)
            self.refit_wave(dirty)
        return results

    def _decode_budget(self, decode_sids) -> Optional[float]:
        """Remaining decode latency budget in microseconds — the minimum
        over the protected decoders' per-request deadlines tracked in the
        scheduler (consumed = the larger of the planned prefill cost charged
        since each session's last decode and the real wall time since it);
        the decode wave's own predicted cost is reserved up front, because
        the inter-token gap the SLO bounds ends when the decode wave's
        tokens *exist*, not when it starts."""
        if self.cost_model is None:
            return None
        # c_dec(B, K): one fused K-token wave, not K times a single step —
        # the fused kernel amortizes the dispatch constant over K, which is
        # exactly why multi-token decode waves are worth planning.
        reserve = self.cost_model.predict_decode_us(len(decode_sids),
                                                    self.decode_wave_tokens)
        return self.scheduler.decode_budget(reserve, among=decode_sids)

    def _decode_due(self, decode_sids) -> None:
        """Run the interleaved decode wave(s) for the *due* subset of the
        protected decoders — the sessions whose per-request deadline is (or
        is about to be) violated; with one engine-wide SLO every budget
        ties, so the due set is the whole protected set and the schedule is
        bit-identical to the old global-clock planner.  Sessions with
        queued open-loop inputs are advanced teacher-driven
        (:meth:`_driven_wave`); the rest free-run."""
        reserve = (self.cost_model.predict_decode_us(
            len(decode_sids), self.decode_wave_tokens)
            if self.cost_model is not None else 0.0)
        due = self.scheduler.due_decode_sids(reserve, among=decode_sids)
        if not due:
            due = list(decode_sids)
        driven = [s for s in due if self.input_depth(s) > 0]
        free = [s for s in due if self.input_depth(s) == 0]
        if free and self.cfg.d_in == self.cfg.d_out:
            self._decode_wave(free)
        if driven:
            self._driven_wave(driven)

    def _dispatch_decode(self, launch, sids, *, tokens: int,
                         block: bool, interleave: bool = False,
                         kind: str = "closed_loop"):
        """Shared wrapper around every decode dispatch: optional wall timing
        (always when ``block``, else only under autotune), decode-surface
        observation (autotune only — there every prefill wave was itself
        synced, so the wall time is decode alone; in pipelined serving a
        block also drains queued prefill waves, and that drain time would
        poison the fit), and the gap/counter/deadline accounting.
        ``launch`` performs the jitted call, stores the new arena, and
        returns the output array to block on; unblocked, the dispatch joins
        the in-flight window."""
        timed = (block or self._autotune) and sids and tokens
        t0 = time.perf_counter() if timed else None
        out = launch()
        us = None
        if t0 is not None:
            with span("serve.block"):
                jax.block_until_ready(out)
            # ``out`` is downstream of every queued prefill wave (they share
            # the arena), so the whole in-flight window just materialized —
            # retire it without paying another block per entry.
            self._window_settled()
            us = (time.perf_counter() - t0) * 1e6
            if self._autotune:
                # The whole K-token wave is ONE observation on the
                # c_dec(B, K) surface — dividing by K would erase the very
                # dispatch amortization the fused kernel buys.
                self.cost_model.observe_decode(len(sids), us, k=tokens)
        elif self.pipeline_depth > 0:
            pred = (self.cost_model.predict_decode_us(len(sids), tokens)
                    if self.cost_model is not None and sids and tokens
                    else 1.0)
            self._inflight_admit(out, pred)
        if sids and tokens:
            self._note_decode(sids, us=us, tokens=tokens,
                              interleave=interleave, kind=kind)
        return out

    def _decode_wave(self, sids: List) -> None:
        """One interleaved decode wave: advance every due decoder by
        ``decode_wave_tokens`` free-running tokens, buffered for
        ``collect_decoded``.

        The wave **always blocks** until its tokens exist: the decode SLO is
        a *latency* contract, and on an async backend a dispatched-but-
        unmaterialized token is still latency — blocking here is what makes
        the inter-token gap statistics (and the deadline reset) real wall
        time, and it drains the queued prefill waves the tokens depend on.
        """
        mask = np.zeros((self.max_slots,), bool)
        for sid in sids:
            st = self.table.sessions[sid]
            mask[st.slot] = True
            st.tokens_decoded += self.decode_wave_tokens
            st.last_use = self.table.tick()

        def launch():
            with span("serve.dispatch", program="closed_loop_fused"):
                self.arena, ys = self._closed_jit(
                    self.params, self._wave_w(), self.arena, mask,
                    int(self.decode_wave_tokens), self._ens_weights)
            return ys

        ys = self._dispatch_decode(launch, sids,
                                   tokens=self.decode_wave_tokens,
                                   block=True, interleave=True,
                                   kind="interleave")
        self.note_freerun(sids, self.decode_wave_tokens)
        self._buffer_wave(ys, sids)

    def _driven_wave(self, sids: List) -> None:
        """One interleaved *teacher-driven* decode wave: drain up to
        ``decode_wave_tokens`` queued per-session inputs (capped by the
        shallowest queue in the wave, so every row steps the same K) through
        ONE ``arena.driven_loop`` dispatch.  Bit-identical to K sequential
        ``decode_step`` calls on the same inputs (pinned by test), so
        caller-driven open-loop sessions get the same SLO protection as
        free-running ones.  Driven tokens count as free-run for the learn
        plane: no ``observe`` ran between them, so they must break the
        teacher pairing rather than fabricate training rows."""
        k = min([self.decode_wave_tokens]
                + [self.input_depth(s) for s in sids])
        if k < 1:
            return
        u_seq = np.zeros((k, self.max_slots, self.cfg.d_in), self._dtype)
        mask = np.zeros((self.max_slots,), bool)
        for sid in sids:
            st = self.table.sessions[sid]
            rows = self.pop_inputs(sid, k)
            u_seq[:, st.slot] = np.stack(rows)
            mask[st.slot] = True
            st.tokens_decoded += k
            st.last_use = self.table.tick()

        def launch():
            with span("serve.dispatch", program="driven_loop"):
                self.arena, ys = self._driven_jit(
                    self.params, self._wave_w(), self.arena, mask, u_seq,
                    self._ens_weights)
            return ys

        ys = self._dispatch_decode(launch, sids, tokens=k, block=True,
                                   interleave=True, kind="driven")
        self.note_freerun(sids, k)
        self._buffer_wave(ys, sids)

    def _buffer_wave(self, ys, sids) -> Dict[Hashable, _Rows]:
        """Buffer one decode wave's output for ``collect_decoded``: a handle
        on the whole of ``ys`` (its host copy starts now) and each
        session's slot, never a per-session device slice."""
        if not sids:
            return {}
        wave = _Wave(ys)
        rows = {sid: _Rows(wave, self.table.sessions[sid].slot)
                for sid in sids}
        for sid, r in rows.items():
            self._decode_buf.setdefault(sid, []).append(r)
        return rows

    def collect_decoded(self, sid: Optional[Hashable] = None) -> DecodeResult:
        """Drain the decoded tokens every decode path buffered, as one
        :class:`DecodeResult` of host numpy arrays (``sid`` -> its
        ``(n_tokens, D_out)`` rows, in decode order).  Each decode wave is
        copied to the host once, by the first drain that needs it; a drain
        waits on the device only for a wave not yet computed (``waited``).
        With ``sid``, drains that session alone (an empty array when
        nothing is buffered).  Buffers clear on read."""
        if sid is not None:
            bufs = {sid: self._decode_buf.pop(sid, [])}
            waves = []
            for meta in list(self._decode_meta):
                pending = meta["_pending"]
                if sid in pending:
                    waves.append({k: v for k, v in meta.items()
                                  if k != "_pending"})
                    pending.discard(sid)
                    if not pending:
                        self._decode_meta.remove(meta)
        else:
            bufs = dict(self._decode_buf)
            self._decode_buf.clear()
            waves = [{k: v for k, v in meta.items() if k != "_pending"}
                     for meta in self._decode_meta]
            self._decode_meta.clear()
        # Which waves still have to cross, and whether each is computed,
        # before any of them is pulled.
        ready: Dict[_Wave, bool] = {}
        unready = 0
        for chunks in bufs.values():
            late = False
            for c in chunks:
                if isinstance(c, _Rows) and not c.wave.pulled:
                    if c.wave not in ready:
                        ready[c.wave] = c.wave.ready()
                    late = late or not ready[c.wave]
            unready += late
        tokens = {s: (np.zeros((0, self.cfg.d_out), self._dtype)
                      if not c else np.asarray(c[0]) if len(c) == 1
                      else np.concatenate([np.asarray(x) for x in c],
                                          axis=0))
                  for s, c in bufs.items()}
        return DecodeResult(tokens=tokens, waves=tuple(waves),
                            pulls=len(ready),
                            pulled_bytes=sum(w.nbytes for w in ready),
                            waited=sum(not r for r in ready.values()),
                            unready=unready)

    def _note_decode(self, sids, *, us=None, tokens: int = 1,
                     interleave: bool = False,
                     kind: str = "closed_loop") -> None:
        """Decode-side accounting shared by every decode path: ONE telemetry
        event (the aggregator derives wall-clock inter-token gaps, wave
        counters, and token totals from it), the per-dispatch metadata
        ``collect_decoded`` reports, and the scheduler's per-request
        deadline reset (a decode just ran for these sessions, so their
        prefill-cost-since-decode budgets restart)."""
        wall = time.perf_counter()
        fused = (kind not in ("step", "driven")
                 and self.params.mode == "diag"
                 and self.readout is not None)
        self._decode_meta.append({"kind": kind, "rows": len(sids),
                                  "tokens": int(tokens), "us": us,
                                  "fused": fused, "_pending": set(sids)})
        self.tracker.log_wave({"kind": "decode", "wall": wall,
                               "sids": list(sids), "rows": len(sids),
                               "tokens": int(tokens), "us": us,
                               "mode": "interleave" if interleave else kind})
        self.scheduler.note_decoded(sids, wall=wall)

    # -------------------------------------------------------------- prefill
    def _run_wave(self, wave: List[WaveItem], capacity: int,
                  results: Dict[Hashable, object], *, method: str,
                  chunk: int, want_outputs: bool) -> None:
        # One batched placement for the whole wave's admissions (per-slot
        # .at[] sets are device dispatches; at wave sizes they'd dwarf the
        # scan).  Continuation rows already own their slot.
        from .ingest import SessionStats
        fresh = [it for it in wave if it.first]
        if fresh:
            h0s = np.zeros((len(fresh), self.cfg.n), self._dtype)
            y0s = np.zeros((len(fresh), self.cfg.d_out), self._dtype)
            slots = []
            for i, it in enumerate(fresh):
                slot = self.table.slots.index(None)
                self.table.slots[slot] = it.sid
                self.table.sessions[it.sid] = SessionStats(
                    slot=slot, prefill_pending=not it.last,
                    last_use=self.table.tick())
                if it.req.h0 is not None:
                    h0s[i] = np.asarray(it.req.h0)
                if it.req.y0 is not None:
                    y0s[i] = np.asarray(it.req.y0)
                slots.append(slot)
                self.note_admission(it.sid, it.req.tenant)
            # The placement writes the whole occupancy mirror from the
            # table: the slots released since the last write ride it.
            with span("serve.dispatch", program="place_many",
                      freed=self._freed):
                self.arena = self._place_jit(
                    self.arena, jnp.asarray(slots), jnp.asarray(h0s),
                    jnp.asarray(y0s), self.table.occupancy())
            self._freed = 0
            # Freshly placed slots must serve their tenant's pooled readout
            # from the first wave, not the engine-wide base.
            self.sync_slot_readouts(
                [(it.sid, s) for it, s in zip(fresh, slots)])
        prompts = [it for it in wave if it.req.u is not None]
        if not prompts:
            self._record_wave(0, len(wave), len(fresh), capacity, 0, None)
            if fresh and self.pipeline_depth > 0 and not self._autotune:
                self._inflight_admit(self.arena.states, 1.0)
            return                  # admission-only wave (bucket 0)
        t_bucket = self._bucket(prompts)
        bw = len(prompts)
        u_pad = np.zeros((bw, t_bucket, self.cfg.d_in), self._dtype)
        lengths = np.zeros((bw,), np.int32)
        yt_pad = (np.zeros((bw, t_bucket, self.cfg.d_out), self._dtype)
                  if self.cfg.use_feedback else None)
        for i, it in enumerate(prompts):
            t = it.length
            u_pad[i, :t] = it.req.u[it.start:it.stop]
            lengths[i] = t
            if yt_pad is not None:
                yt_pad[i, :t] = it.req.y_teacher[it.start:it.stop]
        slots = jnp.asarray([self.table.sessions[it.sid].slot
                             for it in prompts])
        wave_method = method
        if wave_method == "auto" and self.params.mode == "diag":
            wave_method = dispatch.resolve_method(t_bucket, chunk=chunk)
        t0 = None
        if self._autotune:
            # Settle predecessors BEFORE starting the clock: with a non-empty
            # in-flight window, block_until_ready on this wave would also pay
            # for every queued predecessor and the timed c(B,T) record would
            # be inflated by work that isn't this wave's.
            self._drain_inflight()
            t0 = time.perf_counter()
        with span("serve.dispatch", program="prefill_wave"):
            self.arena, out = self._wave_jit(
                self.params, self._wave_w(), self.arena, slots,
                jnp.asarray(u_pad), jnp.asarray(lengths),
                None if yt_pad is None else jnp.asarray(yt_pad),
                method=wave_method, chunk=chunk, want_outputs=want_outputs)
        us = None
        if t0 is not None:
            # Timing a wave means waiting for it — autotune trades a host
            # sync per wave for a cost model that tracks this machine.
            with span("serve.block"):
                jax.block_until_ready(self.arena.states)
            us = (time.perf_counter() - t0) * 1e6
            self.cost_model.observe(bw, t_bucket, us)
        elif self.pipeline_depth == 0:
            # Strict synchronous baseline: materialize every wave before the
            # host plans the next one.  This is the reference the pipelined
            # path must stay bit-exact against.
            tb0 = time.perf_counter()
            with span("serve.block"):
                jax.block_until_ready(self.arena.states)
            self.tracker.log_wave({"kind": "host_block",
                                   "us": (time.perf_counter() - tb0) * 1e6})
        else:
            pred = (self.cost_model.predict_us(bw, t_bucket)
                    if self.cost_model is not None else 1.0)
            self._inflight_admit(self.arena.states, pred)
        tokens = int(lengths.sum())
        self._record_wave(t_bucket, len(wave), len(fresh), capacity,
                          tokens, us)
        # Charge the decode deadlines with what this wave cost (measured
        # when autotune timed it, else the model's prediction): the budget
        # decode-aware flushes plan against is "prefill cost since the last
        # decode wave", whether or not this particular flush is
        # interleaving.
        if us is not None:
            self.scheduler.charge_decode_cost(us)
        elif self.cost_model is not None:
            self.scheduler.charge_decode_cost(
                self.cost_model.predict_us(bw, t_bucket))
        for i, it in enumerate(prompts):
            st = self.table.sessions[it.sid]
            st.tokens_prefilled += int(lengths[i])
            st.last_use = self.table.tick()
            if want_outputs:
                self._chunk_outs.setdefault(it.sid, []).append(
                    out[i, :int(lengths[i])])
            if it.last:
                st.prefill_pending = False
                # The prompt is the washout: the learn plane re-arms the
                # (state, feedback, truth) pairing off the final teacher
                # row.
                self.on_prompt_done(
                    it.sid,
                    None if it.req.y_teacher is None
                    else it.req.y_teacher[it.stop - 1])
                # Pop unconditionally: a want_outputs=False final chunk must
                # still clear chunks recorded by earlier want_outputs=True
                # flushes, or a later session reusing the sid would
                # concatenate this session's stale outputs into its own.
                chunks = self._chunk_outs.pop(it.sid, None)
                if not want_outputs:
                    results[it.sid] = None
                else:
                    results[it.sid] = (chunks[0] if len(chunks) == 1
                                       else jnp.concatenate(chunks, axis=0))

    def _bucket(self, items) -> int:
        """The padded length of a wave of ``items``: the max over its rows
        with a prompt, not the first row's — a padded-up remainder chunk
        (scheduler mixed-kind waves) rides a wave whose bucket is set by its
        longest row; its own padded tail steps are inert.  0 for an
        admission-only wave."""
        return max((bucket_length(it.length,
                                  bucket_min=self.scheduler.bucket_min)
                    for it in items if it.req.u is not None), default=0)

    def _record_wave(self, t_bucket: int, rows: int, fresh: int,
                     capacity: int, tokens: int,
                     us: Optional[float]) -> None:
        self.tracker.log_wave({"kind": "prefill", "t_bucket": t_bucket,
                               "rows": rows, "fresh": fresh,
                               "capacity": capacity, "tokens": tokens,
                               "occupancy": rows / self.max_slots,
                               "us": us})

    # ------------------------------------------------------------- lifecycle
    def place(self, sid, slot: int, h0, y0) -> int:
        n = self.cfg.n
        from .ingest import SessionStats
        h0 = jnp.zeros((n,), self._dtype) if h0 is None else jnp.asarray(h0)
        y0 = (jnp.zeros((self.cfg.d_out,), self._dtype) if y0 is None
              else jnp.asarray(y0))
        self.arena = arena_mod.place(self.arena, slot,
                                     h0.astype(self._dtype),
                                     y0.astype(self._dtype))
        self.table.slots[slot] = sid
        self.table.sessions[sid] = SessionStats(slot=slot)
        self.sync_slot_readouts([(sid, slot)])
        return slot

    def release(self, sid: Hashable, *, drop: bool = False):
        """The one session-release body (see the facade docstring for the
        full contract)."""
        self.scheduler.untrack_decode(sid)
        if self.store is not None and sid in self.store:
            decoded = self.collect_decoded(sid)
            self.tracker.log_wave({"kind": "release", "sid": sid})
            self.pop_learn(sid)
            states, ys, _ = self.store.fetch_many([sid])
            if drop:
                return EvictResult(None, None, decoded)
            return EvictResult(states[0], ys[0], decoded)
        if sid not in self.table.sessions:
            try:
                req = self.scheduler.cancel(sid)
            except KeyError:
                raise KeyError(
                    f"session {sid!r} is neither active nor queued") from None
            self.pop_learn(sid)
            decoded = self.collect_decoded(sid)
            if drop:
                return EvictResult(None, None, decoded)
            return EvictResult(req.h0, req.y0, decoded)
        # Drain the un-collected tokens BEFORE the session bookkeeping goes
        # away: collect_decoded also settles the per-dispatch metadata this
        # sid is still pending in.
        decoded = self.collect_decoded(sid)
        st = self.table.sessions.pop(sid)
        if st.prefill_pending:
            # prefill_pending <=> the chunk remainder is still queued; the
            # scheduler returns it with its progress cursor (see
            # WaveScheduler.cancel) and the arena slot holds the carry.
            self.scheduler.cancel(sid)
        self._chunk_outs.pop(sid, None)
        self.tracker.log_wave({"kind": "release", "sid": sid})
        self.pop_learn(sid)
        if drop:
            state = y = None
        else:
            state = self.arena.states[st.slot]
            y = self.arena.y_prev[st.slot]
        self.table.slots[st.slot] = None
        # No device call: the occupancy mirror catches up at the next
        # placement or read (see ``synced_arena``).
        self._freed += 1
        for req in self.scheduler:
            if req.u is None:
                self.scheduler.cancel(req.sid)
                self.place(req.sid, st.slot, req.h0, req.y0)
                break
        return EvictResult(state, y, decoded)

    def reset(self) -> None:
        self._drain_inflight()
        self.arena = self._fresh_arena()
        self._freed = 0
        self.table.clear()
        if self.store is not None:
            self.store.clear()
        self._chunk_outs.clear()
        self._slot_w = None
        self._decode_buf.clear()
        self._decode_meta.clear()
        self.tracker.log_wave({"kind": "reset"})

    def _active(self, sid: Hashable):
        """Resolve an *admitted, decodable* session, with descriptive errors
        for the natural submit-then-use flow (still queued / chunk waves
        still in flight)."""
        try:
            st = self.table.sessions[sid]
        except KeyError:
            if self.scheduler.has(sid):
                raise KeyError(
                    f"session {sid!r} is queued, not yet admitted — flush() "
                    f"(or wait for an eviction) before using it") from None
            raise
        if st.prefill_pending:
            raise KeyError(
                f"session {sid!r} still has prefill chunk waves in flight — "
                f"flush() until its prompt completes before decoding")
        return st

    def state_of(self, sid: Hashable):
        if self.store is not None and sid in self.store:
            # Read-only peek: inspecting a parked session must not thrash
            # the arena (no promotion).
            return self.store.peek(sid)[0]
        return np.asarray(self.arena.states[self._active(sid).slot])

    # ---------------------------------------------------------------- decode
    def decode_step(self, inputs: Dict[Hashable, "np.ndarray"]):
        """The batched one-token decode body (see the facade docstring)."""
        # Parked sessions promote transparently (paged engine) before the
        # resolve: decode on a parked sid is the promotion trigger.
        self._ensure_hot(list(inputs))
        # Resolve every sid and validate every vector before mutating
        # anything: a bad input must not leave other sessions' stats
        # half-updated.
        stats = {sid: self._active(sid) for sid in inputs}
        vecs = {sid: np.asarray(vec).reshape(self.cfg.d_in)
                for sid, vec in inputs.items()}
        u = np.zeros((self.max_slots, self.cfg.d_in), self._dtype)
        mask = np.zeros((self.max_slots,), bool)
        for sid, vec in vecs.items():
            st = stats[sid]
            u[st.slot] = vec
            mask[st.slot] = True
            st.tokens_decoded += 1
            st.last_use = self.table.tick()
        # One teacher-forcible step elapsed: the learn plane's pairing
        # counter (a training pair forms only when exactly one step
        # separates consecutive teacher events).
        self.note_steps(list(vecs))

        def launch():
            with span("serve.dispatch", program="decode_step"):
                self.arena, y = self._decode_jit(
                    self.params, self._wave_w(), self.arena, u, mask,
                    self._ens_weights)
            return y

        y = self._dispatch_decode(launch, list(vecs), tokens=1, block=False,
                                  kind="step")
        if self.learn_active():
            # The learn plane snapshots the post-step arena in ONE batched
            # D2H pull for the observe() accumulation that typically
            # follows.
            self.cache_post_step(self.arena)
        if self.readout is None:
            return {}
        y = np.asarray(y)
        out = {sid: y[self.table.sessions[sid].slot] for sid in inputs}
        for sid in out:
            # Sessions that grew DPG ensemble members return the validation-
            # RMSE-weighted vote over primary + members (the members advance
            # in the learn plane, teacher-driven off the same input).
            out[sid] = self.vote(sid, vecs[sid], out[sid])
        for sid, row in out.items():
            # Unified decode surface: single steps buffer as (1, D) rows so
            # collect_decoded() drains every path the same way.
            self._decode_buf.setdefault(sid, []).append(
                np.asarray(row)[None])
        return out

    def observe(self, sid: Hashable, y_true):
        """The teacher-forcing body (see the facade docstring)."""
        self._ensure_hot([sid])        # a parked sid promotes transparently
        st = self._active(sid)
        st.last_use = self.table.tick()
        y = jnp.asarray(y_true, self._dtype).reshape(self.cfg.d_out)
        # Streaming accumulation (learn=True) happens in the learn plane:
        # it reads the PRE-observe arena rows (or its own post-step
        # snapshot), so it must run before the arena rewrite below.
        self.on_observe(sid, st.slot, y, self.arena)
        # The mean-ensemble branch rewrites every ready session's feedback
        # row.
        if self.ensemble == "mean":
            slots = jnp.asarray([self.table.sessions[s].slot
                                 for s in self.table.ready])
            self.arena = dataclasses.replace(
                self.arena,
                y_prev=self.arena.y_prev.at[slots].set(y))
            return
        self.arena = arena_mod.force_output(self.arena, st.slot, y)

    def decode_closed_loop(self, n_steps: int, sids=None):
        """Free-run ``n_steps`` closed-loop tokens for ``sids`` (default:
        every ready session) in one fused dispatch.  The tokens buffer for
        :meth:`collect_decoded`; the return maps each sid to its
        ``(n_steps, D_out)`` host rows, read from the wave's one host copy
        on first access, so a loop that drains through ``collect_decoded``
        stays asynchronous here."""
        if self.readout is None:
            raise ValueError("closed-loop decode needs a trained readout")
        if self.cfg.d_in != self.cfg.d_out:
            raise ValueError("closed loop requires d_in == d_out")
        # dict.fromkeys: dedupe (a repeated sid must not double-count tokens)
        # while preserving order; values resolved via _active for clear
        # errors.  Default: the *ready* sessions — chunk-in-flight sessions
        # hold slots but must not free-run mid-prompt.
        targets = list(dict.fromkeys(
            self.table.ready if sids is None else sids))
        self._ensure_hot(targets)      # parked targets promote transparently
        stats = {sid: self._active(sid) for sid in targets}  # validate first
        mask = np.zeros((self.max_slots,), bool)
        for sid in targets:
            mask[stats[sid].slot] = True
            stats[sid].tokens_decoded += n_steps
            stats[sid].last_use = self.table.tick()

        def launch():
            with span("serve.dispatch", program="closed_loop_fused"):
                self.arena, ys = self._closed_jit(
                    self.params, self._wave_w(), self.arena, mask,
                    int(n_steps), self._ens_weights)
            return ys

        # Autotune times the dispatch (host sync, the price of a
        # measurement) — the per-token cost feeds the decode surface the
        # decode-aware planner budgets against.
        ys = self._dispatch_decode(launch, targets, tokens=n_steps,
                                   block=False)
        self.note_freerun(targets, n_steps)
        # ys: (n_steps, max_slots, d_out).  Nothing waits on the device
        # here: the wave's host copy starts now and is read once, when its
        # tokens are drained (or the returned mapping is indexed).
        return _WaveTokens(self._buffer_wave(ys, targets))

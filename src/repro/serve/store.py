"""SessionStore — tiered session state: the arena is a cache, not the truth.

The paper's O(N) diagonal update makes per-session serving state tiny — one
``(N,)`` state vector plus the ``(D_out,)`` feedback output — so the binding
capacity limit in the serving stack is not compute but the ``max_slots``
device arena.  This module splits **session** from **slot** (the way a paged
KV cache splits sequences from pages): the ``SlotArena`` holds only the *hot*
sessions, and everything else lives in two colder tiers owned by
:class:`SessionStore`:

* **host tier** — a preallocated pinned pool of ``(state, y_prev)`` rows
  (:class:`HostPool`).  Demotion gathers the victim slots' rows in ONE
  device->host transfer per wave; promotion scatters them back in ONE
  ``place_many``.  Page waves are priced by the ``WaveCostModel``'s
  ``kind: "page"`` surface, so they compete with prefill and decode under
  the same latency budget.
* **cold tier** — per-session ``.npz`` records under ``cold_dir``, keyed by
  a store **epoch** (modeled on ``train/checkpoint.py``; fsspec URLs work
  when fsspec is importable, plain paths always).  When the host pool fills,
  its LRU rows spill here; a restored engine bumps the epoch so new records
  never collide with the ones an old snapshot still references.

The store owns the *parked*-session table (sid -> tier + location + the
engine's per-session accounting struct, carried through park/restore
untouched).  The engine (``serve.engine``) stays the owner of the *hot*
table; movement between the tiers is always whole waves:
``park_many`` (demote) and ``fetch_many`` (promote/evict) move K sessions
with one pool copy or one batch of record reads.

**Async I/O lane** (``io_workers``): host->cold spills and cold->host
prefetches run on a small thread pool with **per-session futures** — the
table metadata (tier, path) updates synchronously, only the file bytes move
in the background.  A caller blocks on a session's future *only when its
data is actually needed* (``fetch_many`` / ``peek`` / ``drain_io``), so a
demote wave's spill overlaps the next wave's device scan instead of
serializing behind ``np.savez``.  Every prefetch future is tagged with the
store **epoch at submit time**: a completion that lands after the epoch has
moved on (an engine restore) is discarded and the record re-read from the
current table's path, so async completion order can never resurrect a stale
epoch's data (pinned by hypothesis property).  ``io_workers=0`` restores
fully synchronous I/O — the bit-exact baseline the pipelined engine is
tested against.

Paging is exact by construction: rows move through ``jax.device_get`` /
host->device ``place_many`` with no dtype change, so a
park -> spill -> restore round trip is bit-identical to never parking
(pinned by test across all three tiers).

The capstone is :func:`snapshot_engine` / :func:`restore_engine`: the whole
serving process — arena, hot + parked session tables, admission queue with
chunk cursors, un-collected decode buffers, and the cost-model artifact —
serialized to one directory (npz + JSON manifest + ``_COMPLETE`` marker,
atomic tmp-rename), so a process can be drained, upgraded, and resumed
bit-exactly mid-workload.  Cold-tier records are *referenced*, not copied:
they are already durable storage.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, Hashable, List, Optional, Tuple

import numpy as np

__all__ = ["HostPool", "ParkedSession", "SessionStore",
           "snapshot_engine", "restore_engine"]

try:                                     # optional: URL-addressed cold tiers
    import fsspec as _fsspec
except Exception:                        # pragma: no cover - env dependent
    _fsspec = None

#: Snapshot manifest schema version (bump on incompatible layout changes).
SNAPSHOT_VERSION = 1


def _is_url(path: str) -> bool:
    return "://" in str(path)


def _open(path: str, mode: str):
    if _fsspec is not None and _is_url(path):
        return _fsspec.open(path, mode).open()
    return open(path, mode)


def _makedirs(path: str) -> None:
    if _is_url(path):
        if _fsspec is not None:
            fs, p = _fsspec.core.url_to_fs(path)
            fs.makedirs(p, exist_ok=True)
        return
    os.makedirs(path, exist_ok=True)


def _sid_from_json(x):
    """Invert JSON's tuple->list coercion: session ids may be strs, ints, or
    (nested) tuples thereof — a list can never be a real sid (unhashable), so
    every list in a manifest is a tuple that went through ``json.dump``."""
    if isinstance(x, list):
        return tuple(_sid_from_json(v) for v in x)
    return x


class HostPool:
    """Preallocated host-memory ring of parked ``(state, y_prev)`` rows.

    Allocation is free-list based: rows are reused in place, never grown —
    the pool's footprint is fixed at construction (``rows * (N + D_out)``
    elements), which is what makes it safe to size against host RAM up
    front.  NumPy arrays are page-locked-adjacent in practice on CPU
    backends; on accelerator backends the batched ``device_get`` /
    ``device_put`` path amortizes the transfer per wave either way.
    """

    def __init__(self, rows: int, n: int, d_out: int, dtype):
        if rows < 1:
            raise ValueError(f"HostPool needs >= 1 row, got {rows}")
        self.states = np.zeros((rows, n), dtype)
        self.y_prev = np.zeros((rows, d_out), dtype)
        self._free: List[int] = list(range(rows - 1, -1, -1))

    @property
    def rows(self) -> int:
        return self.states.shape[0]

    @property
    def free(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("host pool exhausted")
        return self._free.pop()

    def release(self, row: int) -> None:
        self._free.append(row)


@dataclasses.dataclass
class ParkedSession:
    """One parked session: where its state lives and the engine's accounting
    struct (``serve.engine.SessionStats``, carried opaquely — ``slot`` is -1
    while parked; ``last_use`` is the LRU key for host->cold spill)."""
    stats: object
    tier: str                            # "host" | "cold"
    row: Optional[int] = None            # host pool row (tier == "host")
    path: Optional[str] = None           # npz record  (tier == "cold")


class SessionStore:
    """The parked-session table over the host and cold tiers.

    Host-only module state (numpy + file IO; no jax) — the engine does the
    device transfers and hands this store plain host arrays.  All movement
    is wave-granular: :meth:`park_many` / :meth:`fetch_many` take K sessions
    at once and touch the pool with one fancy-index copy.
    """

    def __init__(self, n: int, d_out: int, dtype, *, host_rows: int,
                 cold_dir: Optional[str] = None, epoch: int = 0,
                 io_workers: int = 2, _executor=None):
        self.n = int(n)
        self.d_out = int(d_out)
        self.dtype = np.dtype(dtype)
        self.pool = HostPool(host_rows, n, d_out, dtype)
        self.cold_dir = cold_dir
        self.epoch = int(epoch)
        self._seq = 0                    # per-epoch cold record counter
        self.table: Dict[Hashable, ParkedSession] = {}
        # Async I/O lane: spill writes and prefetch reads run here; the
        # executor is created lazily (most stores never spill).  io_workers=0
        # keeps every file touch synchronous.  ``_executor`` is a test seam:
        # injecting a manually-stepped executor lets the epoch-guard property
        # drive completions in adversarial orders deterministically.
        self.io_workers = int(io_workers)
        self._io = _executor
        #: sid -> Future of an in-flight host->cold record write.
        self._spills: Dict[Hashable, Future] = {}
        #: sid -> (submit-time epoch, Future of a cold->host record read).
        self._prefetch: Dict[Hashable, Tuple[int, Future]] = {}

    # ------------------------------------------------------------ async I/O
    def _executor_or_none(self):
        if self._io is None and self.io_workers > 0:
            self._io = ThreadPoolExecutor(
                max_workers=self.io_workers,
                thread_name_prefix="session-store-io")
        return self._io

    def _write_record(self, path: str, state, y_prev) -> None:
        with _open(path, "wb") as f:
            np.savez(f, state=state, y_prev=y_prev)

    def _read_record(self, path: str) -> Tuple[np.ndarray, np.ndarray]:
        with _open(path, "rb") as f:
            with np.load(f) as rec:
                return rec["state"].copy(), rec["y_prev"].copy()

    def _wait_spill(self, sid: Hashable) -> None:
        """Resolve ``sid``'s in-flight spill write, if any — the one point a
        cold read may block on a pending write (write errors surface here,
        at the first use of the data, not silently in a worker thread)."""
        fut = self._spills.pop(sid, None)
        if fut is not None:
            fut.result()

    def prefetch_many(self, sids) -> int:
        """Start cold->host reads for the cold-tier sessions in ``sids``;
        returns how many reads were submitted.  Purely advisory: the data
        lands in per-session futures that :meth:`fetch_many` consumes — a
        prefetch never mutates the table, and a prefetch whose epoch goes
        stale before consumption is discarded unread (the epoch guard).
        No-op with ``io_workers=0``."""
        ex = self._executor_or_none()
        if ex is None:
            return 0
        n = 0
        for sid in sids:
            entry = self.table.get(sid)
            if (entry is None or entry.tier != "cold"
                    or sid in self._prefetch):
                continue
            spill = self._spills.get(sid)
            path = entry.path

            def task(path=path, spill=spill):
                if spill is not None:   # record may still be being written
                    spill.result()
                return self._read_record(path)

            self._prefetch[sid] = (self.epoch, ex.submit(task))
            n += 1
        return n

    def drain_io(self) -> None:
        """Block until every in-flight spill and prefetch has completed.
        Spill errors propagate; prefetch results stay buffered (fresh) or
        are dropped (stale epoch).  Snapshotting calls this so every cold
        record the manifest references is durable on disk."""
        for sid in list(self._spills):
            self._wait_spill(sid)
        for sid, (epoch, fut) in list(self._prefetch.items()):
            fut.result()
            if epoch != self.epoch:
                self._prefetch.pop(sid, None)

    # ------------------------------------------------------------- queries
    def __contains__(self, sid: Hashable) -> bool:
        return sid in self.table

    def __len__(self) -> int:
        return len(self.table)

    @property
    def sids(self) -> List[Hashable]:
        return list(self.table)

    def tier_of(self, sid: Hashable) -> str:
        return self.table[sid].tier

    def stats(self) -> dict:
        host = sum(1 for e in self.table.values() if e.tier == "host")
        return {"parked": len(self.table), "host": host,
                "cold": len(self.table) - host,
                "host_rows": self.pool.rows,
                "host_rows_free": self.pool.free,
                "epoch": self.epoch,
                "io_spills_inflight": len(self._spills),
                "io_prefetch_inflight": len(self._prefetch)}

    # ------------------------------------------------------------- parking
    def park_many(self, sids, states, y_prevs, stats_list) -> None:
        """Park K demoted sessions into the host tier.  ``states``:
        (K, N) host array (the engine's batched ``device_get`` of the victim
        slots); ``y_prevs``: (K, D_out); ``stats_list``: the engine's
        per-session structs, kept verbatim for the eventual promote.  When
        the pool is short, its LRU rows spill to the cold tier first — the
        *incoming* sessions are by definition hotter than the LRU parked
        ones (they were on device a moment ago)."""
        sids = list(sids)
        if not sids:
            return
        for sid in sids:
            if sid in self.table:
                raise KeyError(f"session {sid!r} already parked")
        short = len(sids) - self.pool.free
        if short > 0:
            self._spill(short)
        states = np.asarray(states, self.dtype)
        y_prevs = np.asarray(y_prevs, self.dtype)
        for i, (sid, st) in enumerate(zip(sids, stats_list)):
            row = self.pool.alloc()
            self.pool.states[row] = states[i]
            self.pool.y_prev[row] = y_prevs[i]
            self.table[sid] = ParkedSession(stats=st, tier="host", row=row)

    def _spill(self, k: int) -> None:
        """Move the K least-recently-used host-tier sessions to cold
        records.  Raises when there is no cold tier to spill into — a fixed
        pool with no backing store is a hard capacity config, and silently
        dropping state is never an option."""
        host = [(getattr(e.stats, "last_use", 0), sid)
                for sid, e in self.table.items() if e.tier == "host"]
        if len(host) < k:
            raise RuntimeError(
                f"host pool needs {k} more row(s) but only {len(host)} "
                f"host-tier session(s) exist to spill — host_rows="
                f"{self.pool.rows} is too small for this demote wave")
        if self.cold_dir is None:
            raise RuntimeError(
                f"host pool full ({self.pool.rows} rows) and no cold_dir "
                f"configured — pass cold_dir= to spill LRU sessions to disk")
        host.sort()
        ex = self._executor_or_none()
        for _, sid in host[:k]:
            entry = self.table[sid]
            path = self._cold_path()
            if ex is not None:
                # Async lane: snapshot the row (the pool slot is reused the
                # moment it is released) and let the write land in the
                # background — the table flips to cold *now*, only the bytes
                # are in flight.  Readers resolve the future via _wait_spill.
                state = self.pool.states[entry.row].copy()
                y_prev = self.pool.y_prev[entry.row].copy()
                self._spills[sid] = ex.submit(self._write_record, path,
                                              state, y_prev)
            else:
                self._write_record(path, self.pool.states[entry.row],
                                   self.pool.y_prev[entry.row])
            self.pool.release(entry.row)
            entry.tier, entry.row, entry.path = "cold", None, path

    def _cold_path(self) -> str:
        base = f"epoch_{self.epoch:04d}"
        sep = "/" if _is_url(self.cold_dir) else os.sep
        _makedirs(f"{self.cold_dir}{sep}{base}")
        path = f"{self.cold_dir}{sep}{base}{sep}s{self._seq:06d}.npz"
        self._seq += 1
        return path

    # ----------------------------------------------------------- restoring
    def fetch_many(self, sids) -> Tuple[np.ndarray, np.ndarray, list]:
        """Remove K parked sessions and return ``(states (K, N),
        y_prevs (K, D_out), stats_list)`` — the promote/evict read.  Host
        rows are copied out and freed; cold records are read (their files
        are left in place: records are append-only within an epoch and
        reclaimed wholesale when the epoch directory is dropped)."""
        sids = list(sids)
        states = np.zeros((len(sids), self.n), self.dtype)
        y_prevs = np.zeros((len(sids), self.d_out), self.dtype)
        stats_list = []
        for i, sid in enumerate(sids):
            entry = self.table.pop(sid)
            if entry.tier == "host":
                states[i] = self.pool.states[entry.row]
                y_prevs[i] = self.pool.y_prev[entry.row]
                self.pool.release(entry.row)
            else:
                states[i], y_prevs[i] = self._read_cold(sid, entry)
            stats_list.append(entry.stats)
        return states, y_prevs, stats_list

    def _read_cold(self, sid: Hashable,
                   entry: ParkedSession) -> Tuple[np.ndarray, np.ndarray]:
        """One cold record, preferring a completed prefetch.  This is the
        epoch guard: a prefetch submitted under an older epoch is discarded
        unread — whatever its completion order relative to the epoch bump —
        and the record re-read from the entry's (current-table) path."""
        pre = self._prefetch.pop(sid, None)
        if pre is not None:
            epoch, fut = pre
            if epoch == self.epoch:
                return fut.result()    # blocks only if still in flight
            # Stale epoch: drop the buffered read on the floor.  The future
            # may still be running; its result is never observed.
        self._wait_spill(sid)
        return self._read_record(entry.path)

    def peek(self, sid: Hashable) -> Tuple[np.ndarray, np.ndarray]:
        """Read a parked session's ``(state, y_prev)`` without promoting it
        (``engine.state_of`` on a parked sid)."""
        entry = self.table[sid]
        if entry.tier == "host":
            return (self.pool.states[entry.row].copy(),
                    self.pool.y_prev[entry.row].copy())
        self._wait_spill(sid)
        return self._read_record(entry.path)

    def clear(self) -> None:
        """Drop every parked session (engine ``reset``).  Cold files are left
        on disk — epochs are reclaimed by deleting their directories, never
        by the store guessing which records are dead.  In-flight spill
        writes are left to finish in the background (their files are as dead
        as the synchronous ones); buffered prefetches are dropped."""
        for entry in self.table.values():
            if entry.tier == "host":
                self.pool.release(entry.row)
        self.table.clear()
        self._spills.clear()
        self._prefetch.clear()


# ====================================================================== #
#  Engine snapshot / restore                                             #
# ====================================================================== #

def _params_arrays(params):
    """(class name, present leaf names, {key: np array}) for a param struct —
    the manifest records which optional leaves (w_fb / wfb_q) exist."""
    from ..core.params import DiagParams
    names = (("lam_q", "win_q", "wfb_q", "qtq")
             if isinstance(params, DiagParams) else ("w", "w_in", "w_fb"))
    present, arrays = [], {}
    for name in names:
        v = getattr(params, name)
        if v is not None:
            present.append(name)
            arrays[f"params/{name}"] = np.asarray(v)
    return type(params).__name__, present, arrays


def _stats_rec(sid, st) -> dict:
    return {"sid": sid, "slot": st.slot, "tp": st.tokens_prefilled,
            "td": st.tokens_decoded, "pending": st.prefill_pending,
            "last_use": st.last_use}


def _stats_from_rec(rec):
    from .ingest import SessionStats
    return SessionStats(slot=rec["slot"], tokens_prefilled=rec["tp"],
                        tokens_decoded=rec["td"],
                        prefill_pending=rec["pending"],
                        last_use=rec["last_use"])


def snapshot_engine(engine, path: str) -> str:
    """Serialize a whole serving engine to ``path`` (a directory).

    Captures everything a bit-exact resume needs: params + readout, the
    arena arrays, hot and parked session tables, the admission queue with
    chunk cursors and parked ``(h0, y0)``, un-collected decode buffers and
    wave metadata, the scheduler's committed deferral, and the cost-model
    artifact (``cost.json``, the same schema ``WaveCostModel.from_artifact``
    reads).  Host-tier parked rows are embedded; cold-tier records are
    referenced by path (they are already durable).  The write is atomic:
    ``<path>.tmp`` is renamed over ``path`` only after the ``_COMPLETE``
    marker lands (the ``train/checkpoint.py`` contract).  Cumulative
    ``stats()`` counters are *not* carried — a restored engine's telemetry
    starts fresh.  Returns ``path``.
    """
    manifest: dict = {"version": SNAPSHOT_VERSION}
    arrays: Dict[str, np.ndarray] = {}

    pcls, present, parrs = _params_arrays(engine.params)
    arrays.update(parrs)
    manifest["params"] = {"class": pcls, "arrays": present,
                          "cfg": dataclasses.asdict(engine.cfg),
                          "n_real": int(getattr(engine.params, "n_real", 0))}
    manifest["dtype"] = str(np.dtype(engine._dtype))
    manifest["readout"] = engine.readout is not None
    if engine.readout is not None:
        arrays["readout/w_out"] = np.asarray(engine.readout.w_out)

    sched = engine.scheduler
    manifest["engine"] = {
        "max_slots": engine.max_slots,
        "bucket_min": sched.bucket_min,
        "max_wave": sched.max_wave,
        "chunk_max": sched.chunk_max,
        "ensemble": engine.ensemble,
        "autotune": engine._autotune,
        "decode_slo_us": engine.decode_slo_us,
        # "auto" survives the round trip: the restored engine re-resolves K
        # per flush rather than freezing the last resolved value.
        "decode_wave_tokens": ("auto" if engine._decode_k_auto
                               else engine.decode_wave_tokens),
        "pipeline_depth": engine.pipeline_depth,
        "param_batch": engine._batched,
        "park_host_rows": engine._park_host_rows,
        "cold_dir": engine._cold_dir,
        "learn": engine._learn,
        "refit_alpha": engine._refit_alpha,
        "refit_decay": engine._refit_decay,
        "refit_washout": engine._refit_washout,
        "drift_threshold": engine._drift_threshold,
        "drift_beta": engine._drift_beta,
        "growth_max_members": engine._growth_max,
        "growth_sigma": engine._growth_sigma,
        "growth_washout": engine._growth_washout,
    }
    manifest["use_clock"] = engine._use_clock

    # Per-tenant readout pools + per-session streaming learn state.  Folded
    # stats only: the engine folds each session's buffered rows first (the
    # snapshot is already a host sync point).  Grown DPG ensemble members
    # are NOT persisted — they are a drift response, and a restored engine
    # re-grows them on drift; their teacher signal is in the stream, not
    # the snapshot.
    pools = []
    for i, (key, w) in enumerate(engine._readouts.items()):
        pools.append({"key": key})
        arrays[f"pool{i}/w"] = np.asarray(w)
    manifest["readout_pools"] = pools
    learn_state = []
    for i, (sid, ls) in enumerate(engine._learn_state.items()):
        engine._fold_acc(ls.acc, engine._session_params(sid)
                         if sid in engine.sessions else engine.params)
        rec = {"sid": sid, "tenant": ls.tenant, "pairs": ls.acc.pairs,
               "skip_left": ls.acc.skip_left, "drift": ls.acc.drift,
               "steps_since_fb": ls.steps_since_fb, "dirty": ls.dirty,
               "gram": ls.acc.gram is not None,
               "last_fb": ls.last_fb is not None}
        if ls.acc.gram is not None:
            arrays[f"learn{i}/gram"] = np.asarray(ls.acc.gram)
            arrays[f"learn{i}/cg"] = np.asarray(ls.acc.cg)
        if ls.last_fb is not None:
            arrays[f"learn{i}/last_fb"] = np.asarray(ls.last_fb)
        learn_state.append(rec)
    manifest["learn_state"] = learn_state

    arrays["arena/states"] = np.asarray(engine.arena.states)
    arrays["arena/y_prev"] = np.asarray(engine.arena.y_prev)
    arrays["arena/active"] = np.asarray(engine.arena.active)
    manifest["sessions"] = [_stats_rec(sid, st)
                            for sid, st in engine.sessions.items()]

    store = engine.store
    if store is not None:
        # The manifest references cold records by path: every in-flight
        # spill write must be durable before the snapshot claims them.
        store.drain_io()
        parked, host_states, host_ys = [], [], []
        for sid, entry in store.table.items():
            rec = {"sid": sid, "tier": entry.tier,
                   "stats": _stats_rec(sid, entry.stats)}
            if entry.tier == "cold":
                rec["path"] = entry.path
            else:
                rec["hrow"] = len(host_states)
                host_states.append(store.pool.states[entry.row])
                host_ys.append(store.pool.y_prev[entry.row])
            parked.append(rec)
        arrays["park/states"] = (np.stack(host_states) if host_states else
                                 np.zeros((0, store.n), store.dtype))
        arrays["park/y_prev"] = (np.stack(host_ys) if host_ys else
                                 np.zeros((0, store.d_out), store.dtype))
        manifest["store"] = {"epoch": store.epoch, "seq": store._seq,
                             "parked": parked}

    queue = []
    for i, req in enumerate(sched._queue):
        rec = {"sid": req.sid, "done": req.done}
        for name in ("u", "y_teacher", "h0", "y0"):
            v = getattr(req, name)
            rec[name] = v is not None
            if v is not None:
                arrays[f"q{i}/{name}"] = np.asarray(v)
        queue.append(rec)
    manifest["queue"] = queue
    manifest["deferred"] = sched._deferred

    bufs = []
    for i, (sid, chunks) in enumerate(engine._decode_buf.items()):
        arrays[f"dec{i}"] = np.concatenate(
            [np.asarray(c) for c in chunks], axis=0)
        bufs.append({"sid": sid})
    manifest["decode_buf"] = bufs
    chunk_outs = []
    for i, (sid, chunks) in enumerate(engine._chunk_outs.items()):
        arrays[f"chunk{i}"] = np.concatenate(
            [np.asarray(c) for c in chunks], axis=0)
        chunk_outs.append({"sid": sid})
    manifest["chunk_outs"] = chunk_outs
    manifest["decode_meta"] = [
        {"kind": m["kind"], "rows": m["rows"], "tokens": m["tokens"],
         "us": m["us"], "fused": m["fused"],
         "pending": sorted(m["_pending"], key=repr)}
        for m in engine._decode_meta]
    manifest["cost"] = None
    if engine.cost_model is not None:
        cm = engine.cost_model
        manifest["cost"] = {
            "key": None if cm.key is None else list(cm.key),
            "base_us": cm.base_us, "per_token_us": cm.per_token_us,
            "decode_base_us": cm.decode_base_us,
            "decode_per_row_us": cm.decode_per_row_us,
            "page_base_us": cm.page_base_us,
            "page_per_row_us": cm.page_per_row_us,
        }

    tmp = str(path) + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    if engine.cost_model is not None:
        engine.cost_model.to_artifact(os.path.join(tmp, "cost.json"))
    with open(os.path.join(tmp, "_COMPLETE"), "w") as f:
        f.write("ok")
    if os.path.exists(path):
        shutil.rmtree(path)
    os.rename(tmp, path)
    return str(path)


def restore_engine(cls, path: str, *, mesh=None):
    """Rebuild a serving engine from :func:`snapshot_engine` output.

    The restored engine resumes bit-exactly: same params/readout, same
    arena contents, same hot/parked/queued sessions (chunk cursors and the
    scheduler's committed deferral included), same un-collected decode
    buffers, and a cost model re-seeded from the snapshot's ``cost.json``.
    The session store's epoch is bumped so new cold records never collide
    with the ones the snapshot references.  ``mesh`` re-places the arena on
    a (possibly different) device mesh — elastic restore, same contract as
    ``train.checkpoint.restore``.  Bit-exactness assumes the same
    ``jax_enable_x64`` setting as the snapshotting process (dtype
    canonicalization happens on device_put).
    """
    import jax
    import jax.numpy as jnp
    from ..core.params import DiagParams, ESNConfig, Readout, StandardParams
    from . import arena as arena_mod
    from .cost import WaveCostModel
    from .scheduler import PrefillRequest

    if not os.path.exists(os.path.join(path, "_COMPLETE")):
        raise FileNotFoundError(
            f"no complete engine snapshot at {path!r} (missing _COMPLETE — "
            f"interrupted write?)")
    with open(os.path.join(path, "manifest.json")) as f:
        m = json.load(f)
    if m.get("version") != SNAPSHOT_VERSION:
        raise ValueError(f"snapshot version {m.get('version')!r} != "
                         f"{SNAPSHOT_VERSION} (incompatible layout)")
    data = np.load(os.path.join(path, "arrays.npz"))

    cfg = ESNConfig(**m["params"]["cfg"])
    pcls = {"DiagParams": DiagParams,
            "StandardParams": StandardParams}[m["params"]["class"]]
    names = (("lam_q", "win_q", "wfb_q", "qtq") if pcls is DiagParams
             else ("w", "w_in", "w_fb"))
    kw = {name: (jnp.asarray(data[f"params/{name}"])
                 if name in m["params"]["arrays"] else None)
          for name in names}
    if pcls is DiagParams:
        params = DiagParams(cfg=cfg, n_real=m["params"]["n_real"], **kw)
    else:
        params = StandardParams(cfg=cfg, **kw)
    readout = (Readout(jnp.asarray(data["readout/w_out"]))
               if m["readout"] else None)

    cost_model = None
    if m["cost"] is not None:
        c = dict(m["cost"])
        key = c.pop("key")
        cost_model = WaveCostModel.from_artifact(
            os.path.join(path, "cost.json"),
            key=None if key is None else tuple(key), **c)

    ek = m["engine"]
    eng = cls(params, max_slots=ek["max_slots"], readout=readout, mesh=mesh,
              bucket_min=ek["bucket_min"], ensemble=ek["ensemble"],
              chunk_max=ek["chunk_max"], autotune=ek["autotune"],
              cost_model=cost_model, decode_slo_us=ek["decode_slo_us"],
              decode_wave_tokens=ek["decode_wave_tokens"],
              park_host_rows=ek["park_host_rows"], cold_dir=ek["cold_dir"],
              pipeline_depth=ek.get("pipeline_depth", 2),
              learn=ek.get("learn", False),
              refit_alpha=ek.get("refit_alpha"),
              refit_decay=ek.get("refit_decay", 1.0),
              refit_washout=ek.get("refit_washout", 0),
              drift_threshold=ek.get("drift_threshold"),
              drift_beta=ek.get("drift_beta", 0.9),
              growth_max_members=ek.get("growth_max_members", 3),
              growth_sigma=ek.get("growth_sigma", 0.1),
              growth_washout=ek.get("growth_washout", 64),
              _param_batch=ek["param_batch"])
    eng.scheduler.max_wave = ek["max_wave"]
    eng._use_clock = m["use_clock"]

    ar = arena_mod.SlotArena(states=jnp.asarray(data["arena/states"]),
                             y_prev=jnp.asarray(data["arena/y_prev"]),
                             active=jnp.asarray(data["arena/active"]))
    if eng._plan is not None:
        ar = arena_mod.SlotArena(
            states=jax.device_put(ar.states, eng._plan.arena["states"]),
            y_prev=jax.device_put(ar.y_prev, eng._plan.arena["y_prev"]),
            active=jax.device_put(ar.active, eng._plan.arena["active"]))
    eng.arena = ar

    for rec in m["sessions"]:
        sid = _sid_from_json(rec["sid"])
        eng.sessions[sid] = _stats_from_rec(rec)
        eng._slots[rec["slot"]] = sid

    # Streaming learn state, then tenant readout pools (in that order: the
    # slot re-scatter below resolves each hot session's pool key through
    # its restored ``tenant``).  Both absent in pre-learn snapshots —
    # ``get`` keeps those restorable.
    for i, rec in enumerate(m.get("learn_state", [])):
        from .learn import _GramAcc, _LearnState
        acc = _GramAcc(pairs=rec["pairs"], skip_left=rec["skip_left"],
                       drift=rec["drift"])
        if rec["gram"]:
            acc.gram = jnp.asarray(data[f"learn{i}/gram"])
            acc.cg = jnp.asarray(data[f"learn{i}/cg"])
        ls = _LearnState(tenant=_sid_from_json(rec["tenant"]),
                         steps_since_fb=rec["steps_since_fb"],
                         dirty=rec["dirty"], acc=acc)
        if rec["last_fb"]:
            ls.last_fb = data[f"learn{i}/last_fb"]
        eng._learn_state[_sid_from_json(rec["sid"])] = ls
    if m.get("readout_pools"):
        for i, rec in enumerate(m["readout_pools"]):
            eng._readouts[_sid_from_json(rec["key"])] = jnp.asarray(
                data[f"pool{i}/w"])
        eng._activate_pool()
        eng._sync_slot_readouts([(sid, st.slot)
                                 for sid, st in eng.sessions.items()])

    if eng.store is not None and "store" in m:
        st = m["store"]
        eng.store.epoch = st["epoch"] + 1        # new records: new epoch dir
        eng.store._seq = 0
        hs, hy = data["park/states"], data["park/y_prev"]
        for rec in st["parked"]:
            sid = _sid_from_json(rec["sid"])
            stats = _stats_from_rec(rec["stats"])
            if rec["tier"] == "host":
                eng.store.park_many([sid], hs[rec["hrow"]][None],
                                    hy[rec["hrow"]][None], [stats])
            else:
                eng.store.table[sid] = ParkedSession(
                    stats=stats, tier="cold", path=rec["path"])

    for i, rec in enumerate(m["queue"]):
        arrs = {name: (data[f"q{i}/{name}"] if rec[name] else None)
                for name in ("u", "y_teacher", "h0", "y0")}
        eng.scheduler.submit(PrefillRequest(
            sid=_sid_from_json(rec["sid"]), done=rec["done"], **arrs))
    if m["deferred"] is not None:
        eng.scheduler._deferred = _sid_from_json(m["deferred"])

    for i, rec in enumerate(m["decode_buf"]):
        eng._decode_buf[_sid_from_json(rec["sid"])] = [data[f"dec{i}"]]
    for i, rec in enumerate(m["chunk_outs"]):
        eng._chunk_outs[_sid_from_json(rec["sid"])] = [
            jnp.asarray(data[f"chunk{i}"])]
    for rec in m["decode_meta"]:
        eng._decode_meta.append(
            {"kind": rec["kind"], "rows": rec["rows"],
             "tokens": rec["tokens"], "us": rec["us"], "fused": rec["fused"],
             "_pending": {_sid_from_json(s) for s in rec["pending"]}})
    return eng

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
(pinned by test across all three tiers).  An engine snapshot
(``serve.snapshot``) embeds the host rows and references the cold records.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, Hashable, List, Optional, Tuple

import numpy as np

__all__ = ["HostPool", "ParkedSession", "SessionStore"]

try:                                     # optional: URL-addressed cold tiers
    import fsspec as _fsspec
except Exception:                        # pragma: no cover - env dependent
    _fsspec = None


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
    struct (``serve.ingest.SessionStats``, carried opaquely — ``slot`` is -1
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

    def cursor(self) -> Tuple[int, int]:
        """``(epoch, seq)``: where the next cold record is written."""
        return self.epoch, self._seq

    def resume_after(self, epoch: int) -> None:
        """Continue a store snapshotted at ``epoch``: new cold records go to
        a fresh epoch directory, so they never collide with the records the
        snapshot references."""
        self.epoch = int(epoch) + 1
        self._seq = 0

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

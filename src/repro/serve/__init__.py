"""Serving subsystem: a three-layer stack for streaming reservoir sessions.

``arena``     — device-side layer: the ``SlotArena`` pytree (``states (B, N)``,
``y_prev``, active mask) + pure ``prefill_wave`` / ``decode_step`` /
``closed_loop`` / ``closed_loop_fused`` functions; placeable on a
multi-device mesh via ``sharding.rules.plan_arena``.
``scheduler`` — host-side admission: requests accumulate, bucket by padded
prompt length (powers of two), and drain as same-bucket waves — each wave is
ONE batched prefill.  Long prompts split into sequential chunk waves
(``chunk_max``), and an optional cost model drives a two-wave lookahead.
``cost``      — ``WaveCostModel``: per-bucket affine wave-cost fits from
measured timings (seeded offline by ``benchmarks/serve_engine.py``, refined
online from engine-recorded wave timings) — what the lookahead plans against,
plus the c_dec(B, K) fused-decode surface.
``engine``    — ``ReservoirEngine``: the thin facade over the four serving
planes (``telemetry`` observability, ``ingest`` control, ``exec_plane``
data, ``learn`` learn-while-serving — one-way imports, enforced by test).
The facade holds the public submit/flush/decode/release lifecycle, wires
the cross-plane callbacks, and merges the planes' snapshots into the typed
``EngineStats``.  Decode tokens drain through ``collect_decoded()`` as one
typed ``DecodeResult`` of host numpy arrays whatever path produced them
(each decode wave's output crosses to the host in one copy, started at
dispatch); with ``learn=True`` the learn plane accumulates streaming
eigenbasis ``(G, C)`` off the ``observe()`` teacher path, refits batched
waves into per-tenant readout pools, and grows DPG ensembles on drift.
``telemetry`` — the pluggable ``Tracker`` protocol (``NullTracker`` /
``JsonlTracker`` / ``MultiTracker``, specs via ``make_tracker``) every
wave/page/refit/decode event flows through, the ``StatsAggregator`` that
derives the ``stats()`` counters from that same stream, and ``span``: the
``serve.*`` host spans a ``jax.profiler`` trace holds beside the device's
ops.
``frontend``  — ``OpenLoopServer``: the asyncio open-loop front end on the
ingest seam (per-token streaming queues, ``AdmissionFull`` backpressure,
graceful drain); ``benchmarks/loadgen.py`` drives it at fixed offered load.
``store``     — ``SessionStore``: tiered session capacity.  The arena is a
*cache of hot sessions* over a pinned host-memory pool and an fsspec/disk
cold tier; a full arena parks its LRU
idle sessions in batched page waves (priced by the cost model's
``kind:"page"`` surface) instead of rejecting admissions, and decode on a
parked session promotes it transparently.  ``snapshot_engine`` /
``restore_engine`` (surfaced as ``engine.snapshot()`` /
``ReservoirEngine.restore()``) serialize the whole serving process for
drain/upgrade/resume.

Backend selection lives in ``core.dispatch`` (the PR-2-era ``serve.dispatch``
re-export shim is gone); ``resolve_method`` / ``run_scan_q`` stay re-exported
here for callers that reach them through the serve namespace.
"""
from . import (arena, cost, engine, exec_plane, frontend, ingest, learn,
               scheduler, store, telemetry)
from ..core.dispatch import resolve_method, run_scan_q
from .arena import SlotArena
from .cost import WaveCostModel, cost_key
from .engine import (DecodeResult, EngineStats, EvictResult, ReservoirEngine,
                     SessionStats)
from .frontend import OpenLoopServer, SessionHandle, StreamToken
from .ingest import AdmissionFull
from .scheduler import PrefillRequest, WaveItem, WaveScheduler, bucket_length
from .store import HostPool, SessionStore
from .telemetry import (JsonlTracker, MultiTracker, NullTracker,
                        StatsAggregator, Tracker, make_tracker)

__all__ = ["arena", "cost", "engine", "exec_plane", "frontend", "ingest",
           "learn", "scheduler", "store", "telemetry",
           "OpenLoopServer", "SessionHandle", "StreamToken",
           "SlotArena", "WaveCostModel", "cost_key",
           "resolve_method", "run_scan_q",
           "DecodeResult", "EngineStats", "EvictResult", "ReservoirEngine",
           "SessionStats", "AdmissionFull",
           "Tracker", "NullTracker", "JsonlTracker", "MultiTracker",
           "StatsAggregator", "make_tracker",
           "PrefillRequest", "WaveItem", "WaveScheduler", "bucket_length",
           "HostPool", "SessionStore"]

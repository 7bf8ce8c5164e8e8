"""Engine snapshot / restore: the whole serving process in one directory.

:func:`snapshot_engine` / :func:`restore_engine` (surfaced as
``ReservoirEngine.snapshot()`` / ``ReservoirEngine.restore()``) write and
read params + readout, the arena, the hot and parked session tables, the
admission queue with chunk cursors, un-collected decode buffers, tenant
readout pools, streaming learn state and the cost-model artifact — npz +
JSON manifest + ``_COMPLETE`` marker, atomic tmp-rename — so a process can
be drained, upgraded, and resumed bit-exactly mid-workload.  Cold-tier
records are *referenced*, not copied: they are already durable storage.

Each piece of state is read from the plane that owns it (``engine._exec``,
``engine._table``, ``engine._learn_plane``, ``engine.scheduler``,
``engine.store``), through that plane's export/load pair where the state is
private.  Only the engine facade imports this module.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
from typing import Dict

import jax.numpy as jnp
import numpy as np

from ..core.params import DiagParams, ESNConfig, Readout, StandardParams
from .arena import SlotArena
from .cost import WaveCostModel
from .ingest import SessionStats
from .learn import _GramAcc, _LearnState
from .scheduler import PrefillRequest
from .store import ParkedSession

__all__ = ["SNAPSHOT_VERSION", "snapshot_engine", "restore_engine"]

#: Snapshot manifest schema version (bump on incompatible layout changes).
SNAPSHOT_VERSION = 1


def _sid_from_json(x):
    """Invert JSON's tuple->list coercion: session ids may be strs, ints, or
    (nested) tuples thereof — a list can never be a real sid (unhashable), so
    every list in a manifest is a tuple that went through ``json.dump``."""
    if isinstance(x, list):
        return tuple(_sid_from_json(v) for v in x)
    return x


def _param_names(pcls) -> tuple:
    return (("lam_q", "win_q", "wfb_q", "qtq") if pcls is DiagParams
            else ("w", "w_in", "w_fb"))


def _params_arrays(params):
    """(class name, present leaf names, {key: np array}) for a param struct —
    the manifest records which optional leaves (w_fb / wfb_q) exist."""
    present, arrays = [], {}
    for name in _param_names(type(params)):
        v = getattr(params, name)
        if v is not None:
            present.append(name)
            arrays[f"params/{name}"] = np.asarray(v)
    return type(params).__name__, present, arrays


def _stats_rec(sid, st) -> dict:
    return {"sid": sid, "slot": st.slot, "tp": st.tokens_prefilled,
            "td": st.tokens_decoded, "pending": st.prefill_pending,
            "last_use": st.last_use}


def _stats_from_rec(rec) -> SessionStats:
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
    ex, table, learn = engine._exec, engine._table, engine._learn_plane
    sched, store = engine.scheduler, engine.store
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

    manifest["engine"] = {
        "max_slots": engine.max_slots,
        "bucket_min": sched.bucket_min,
        "max_wave": sched.max_wave,
        "chunk_max": sched.chunk_max,
        "ensemble": engine.ensemble,
        "autotune": engine._autotune,
        "decode_slo_us": ex.decode_slo_us,
        # "auto" survives the round trip: the restored engine re-resolves K
        # per flush rather than freezing the last resolved value.
        "decode_wave_tokens": ("auto" if ex.decode_k_auto
                               else ex.decode_wave_tokens),
        "pipeline_depth": ex.pipeline_depth,
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
    manifest["use_clock"] = table.use_clock

    # Per-tenant readout pools + per-session streaming learn state.  Folded
    # stats only: the learn plane folds each session's buffered rows first
    # (the snapshot is already a host sync point).  Grown DPG ensemble
    # members are NOT persisted — they are a drift response, and a restored
    # engine re-grows them on drift; their teacher signal is in the stream,
    # not the snapshot.
    pools = []
    for i, (key, w) in enumerate(learn.readouts.items()):
        pools.append({"key": key})
        arrays[f"pool{i}/w"] = np.asarray(w)
    manifest["readout_pools"] = pools
    learn_state = []
    for i, (sid, ls) in enumerate(
            learn.export_state(table.sessions).items()):
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

    state = ex.export_state()
    arena = state["arena"]
    arrays["arena/states"] = np.asarray(arena.states)
    arrays["arena/y_prev"] = np.asarray(arena.y_prev)
    arrays["arena/active"] = np.asarray(arena.active)
    manifest["sessions"] = [_stats_rec(sid, st)
                            for sid, st in table.sessions.items()]

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
        epoch, seq = store.cursor()
        manifest["store"] = {"epoch": epoch, "seq": seq, "parked": parked}

    reqs, deferred = sched.export_queue()
    queue = []
    for i, req in enumerate(reqs):
        rec = {"sid": req.sid, "done": req.done}
        for name in ("u", "y_teacher", "h0", "y0"):
            v = getattr(req, name)
            rec[name] = v is not None
            if v is not None:
                arrays[f"q{i}/{name}"] = np.asarray(v)
        queue.append(rec)
    manifest["queue"] = queue
    manifest["deferred"] = deferred

    bufs = []
    for i, (sid, chunks) in enumerate(state["decode_buf"].items()):
        arrays[f"dec{i}"] = np.concatenate(
            [np.asarray(c) for c in chunks], axis=0)
        bufs.append({"sid": sid})
    manifest["decode_buf"] = bufs
    chunk_outs = []
    for i, (sid, chunks) in enumerate(state["chunk_outs"].items()):
        arrays[f"chunk{i}"] = np.concatenate(
            [np.asarray(c) for c in chunks], axis=0)
        chunk_outs.append({"sid": sid})
    manifest["chunk_outs"] = chunk_outs
    manifest["decode_meta"] = [
        {"kind": m["kind"], "rows": m["rows"], "tokens": m["tokens"],
         "us": m["us"], "fused": m["fused"],
         "pending": sorted(m["_pending"], key=repr)}
        for m in state["decode_meta"]]
    cm = ex.cost_model
    manifest["cost"] = None
    if cm is not None:
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
    if cm is not None:
        cm.to_artifact(os.path.join(tmp, "cost.json"))
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
    kw = {name: (jnp.asarray(data[f"params/{name}"])
                 if name in m["params"]["arrays"] else None)
          for name in _param_names(pcls)}
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
    ex, table, learn = eng._exec, eng._table, eng._learn_plane
    eng.scheduler.max_wave = ek["max_wave"]
    table.use_clock = int(m["use_clock"])

    for rec in m["sessions"]:
        sid = _sid_from_json(rec["sid"])
        table.sessions[sid] = _stats_from_rec(rec)
        table.slots[rec["slot"]] = sid

    # Streaming learn state, then tenant readout pools (in that order: the
    # slot re-scatter below resolves each hot session's pool key through
    # its restored ``tenant``).  Both absent in pre-learn snapshots —
    # ``get`` keeps those restorable.
    states = {}
    for i, rec in enumerate(m.get("learn_state", [])):
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
        states[_sid_from_json(rec["sid"])] = ls
    learn.load_state(states)
    if m.get("readout_pools"):
        for i, rec in enumerate(m["readout_pools"]):
            learn.readouts[_sid_from_json(rec["key"])] = jnp.asarray(
                data[f"pool{i}/w"])
        ex.activate_pool()
        ex.sync_slot_readouts([(sid, st.slot)
                               for sid, st in table.sessions.items()])

    store = eng.store
    if store is not None and "store" in m:
        st = m["store"]
        store.resume_after(st["epoch"])
        hs, hy = data["park/states"], data["park/y_prev"]
        for rec in st["parked"]:
            sid = _sid_from_json(rec["sid"])
            stats = _stats_from_rec(rec["stats"])
            if rec["tier"] == "host":
                store.park_many([sid], hs[rec["hrow"]][None],
                                hy[rec["hrow"]][None], [stats])
            else:
                store.table[sid] = ParkedSession(
                    stats=stats, tier="cold", path=rec["path"])

    reqs = []
    for i, rec in enumerate(m["queue"]):
        arrs = {name: (data[f"q{i}/{name}"] if rec[name] else None)
                for name in ("u", "y_teacher", "h0", "y0")}
        reqs.append(PrefillRequest(
            sid=_sid_from_json(rec["sid"]), done=rec["done"], **arrs))
    eng.scheduler.load_queue(
        reqs, None if m["deferred"] is None
        else _sid_from_json(m["deferred"]))

    ex.load_state(
        SlotArena(states=jnp.asarray(data["arena/states"]),
                  y_prev=jnp.asarray(data["arena/y_prev"]),
                  active=jnp.asarray(data["arena/active"])),
        decode_buf={_sid_from_json(rec["sid"]): [data[f"dec{i}"]]
                    for i, rec in enumerate(m["decode_buf"])},
        decode_meta=[{"kind": rec["kind"], "rows": rec["rows"],
                      "tokens": rec["tokens"], "us": rec["us"],
                      "fused": rec["fused"],
                      "_pending": {_sid_from_json(s)
                                   for s in rec["pending"]}}
                     for rec in m["decode_meta"]],
        chunk_outs={_sid_from_json(rec["sid"]): [
            jnp.asarray(data[f"chunk{i}"])]
            for i, rec in enumerate(m["chunk_outs"])})
    return eng

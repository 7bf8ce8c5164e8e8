"""The engine snapshot's on-disk format, pinned.

``serve/snapshot.py`` writes one directory: ``manifest.json``, ``arrays.npz``,
``cost.json`` and ``_COMPLETE``.  The key set of the manifest (top level, the
engine options, the store section and every record list) and the names in
``arrays.npz`` are pinned below against a paged, learning engine that holds
every kind of state at once: hot sessions, queued and chunked prompts,
parked sessions in both store tiers, a tenant readout pool, streaming
learn state, buffered chunk outputs and undrained decode tokens.  A
snapshot of that engine must also round-trip bit for bit.
"""
import json
import os
import tempfile

import numpy as np

from repro.core import esn as esn_fn
from repro.core.esn import ESNConfig
from repro.data.signals import mso_series
from repro.serve import ReservoirEngine

CFG = ESNConfig(n=24, d_in=1, d_out=1, spectral_radius=0.9, leak=0.8,
                input_scaling=0.5, ridge_alpha=1e-8, use_feedback=True,
                seed=5)

MANIFEST_KEYS = {
    "version", "params", "dtype", "readout", "engine", "use_clock",
    "readout_pools", "learn_state", "sessions", "store", "queue",
    "deferred", "decode_buf", "chunk_outs", "decode_meta", "cost"}
PARAMS_KEYS = {"class", "arrays", "cfg", "n_real"}
ENGINE_KEYS = {
    "max_slots", "bucket_min", "max_wave", "chunk_max", "ensemble",
    "autotune", "decode_slo_us", "decode_wave_tokens", "pipeline_depth",
    "param_batch", "park_host_rows", "cold_dir", "learn", "refit_alpha",
    "refit_decay", "refit_washout", "drift_threshold", "drift_beta",
    "growth_max_members", "growth_sigma", "growth_washout"}
STORE_KEYS = {"epoch", "seq", "parked"}
COST_KEYS = {"key", "base_us", "per_token_us", "decode_base_us",
             "decode_per_row_us", "page_base_us", "page_per_row_us"}
SESSION_REC = {"sid", "slot", "tp", "td", "pending", "last_use"}
LEARN_REC = {"sid", "tenant", "pairs", "skip_left", "drift",
             "steps_since_fb", "dirty", "gram", "last_fb"}
QUEUE_REC = {"sid", "done", "u", "y_teacher", "h0", "y0"}
META_REC = {"kind", "rows", "tokens", "us", "fused", "pending"}
ARRAY_NAMES = {
    "params/lam_q", "params/win_q", "params/wfb_q", "params/qtq",
    "readout/w_out", "pool0/w", "learn0/last_fb", "learn1/last_fb",
    "learn2/gram", "learn2/cg", "learn2/last_fb", "arena/states",
    "arena/y_prev", "arena/active", "park/states", "park/y_prev",
    "q0/u", "q0/y_teacher", "q1/u", "q1/y_teacher", "dec0", "chunk0",
    "chunk1"}


def _engine(cold_dir):
    sig = mso_series(3, 1201)
    params = esn_fn.diag_params(CFG)
    readout = esn_fn.fit(params, sig[:-1, None], sig[1:, None], washout=50)
    u, y = sig[:-1, None], sig[1:, None]
    eng = ReservoirEngine(params, max_slots=2, readout=readout,
                          park_host_rows=1, cold_dir=cold_dir, learn=True,
                          chunk_max=16, pipeline_depth=0)
    # Sessions through a two-slot arena and a one-row host pool, so that
    # both store tiers end up holding parked sessions.
    for sid, lo, tenant in (("a", 100, "t0"), ("b", 140, "t0"),
                            ("c", 180, None)):
        eng.submit(sid, u[lo:lo + 16], y[lo:lo + 16], tenant=tenant)
        eng.flush()
    eng.set_readout("t0", readout)
    eng.submit("d", u[220:236], y[220:236])
    eng.flush()
    for t in range(236, 240):                   # learn rows for "c" and "d"
        eng.decode_step({"c": u[t], "d": u[t]})
        eng.observe("c", y[t])
        eng.observe("d", y[t])
    eng.decode_closed_loop(3, sids=["c"])       # undrained decode tokens
    # A chunked prompt mid-flight (its first chunk's outputs buffered) and
    # a queued prompt that has no slot yet.
    eng.submit("long", u[300:340], y[300:340])
    eng.submit("late", u[400:420], y[400:420])
    eng.release("d")
    eng.flush(max_waves=1, want_outputs=True)
    return eng, u


def _load(path):
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    with np.load(os.path.join(path, "arrays.npz")) as data:
        return manifest, set(data.files)


def test_snapshot_manifest_and_array_names_are_pinned():
    tmp = tempfile.mkdtemp(prefix="snapfmt_")
    eng, _ = _engine(os.path.join(tmp, "cold"))
    path = eng.snapshot(os.path.join(tmp, "snap"))
    m, names = _load(path)
    assert set(m) == MANIFEST_KEYS
    assert m["version"] == 1
    assert set(m["params"]) == PARAMS_KEYS
    assert set(m["engine"]) == ENGINE_KEYS
    assert set(m["store"]) == STORE_KEYS
    assert set(m["cost"]) == COST_KEYS
    assert {p["tier"] for p in m["store"]["parked"]} == {"host", "cold"}
    for rec in m["store"]["parked"]:
        assert set(rec["stats"]) == SESSION_REC
    assert m["sessions"] and all(set(r) == SESSION_REC
                                 for r in m["sessions"])
    assert m["learn_state"] and all(set(r) == LEARN_REC
                                    for r in m["learn_state"])
    assert m["queue"] and all(set(r) == QUEUE_REC for r in m["queue"])
    assert m["decode_meta"] and all(set(r) == META_REC
                                    for r in m["decode_meta"])
    assert m["readout_pools"] == [{"key": "t0"}]
    assert names == ARRAY_NAMES
    assert sorted(os.listdir(path)) == ["_COMPLETE", "arrays.npz",
                                        "cost.json", "manifest.json"]


def test_snapshot_round_trips_bit_exact():
    """Snapshot, restore, snapshot again: every array of the second
    snapshot equals the first's bit for bit, the manifests agree apart from
    the store's epoch cursor, and both engines serve the same tokens."""
    tmp = tempfile.mkdtemp(prefix="snaprt_")
    eng, u = _engine(os.path.join(tmp, "cold"))
    first = eng.snapshot(os.path.join(tmp, "one"))
    back = ReservoirEngine.restore(first)
    second = back.snapshot(os.path.join(tmp, "two"))
    m1, names1 = _load(first)
    m2, names2 = _load(second)
    assert names1 == names2
    with np.load(os.path.join(first, "arrays.npz")) as a, \
            np.load(os.path.join(second, "arrays.npz")) as b:
        for name in names1:
            np.testing.assert_array_equal(a[name], b[name], err_msg=name)
    assert m2["store"]["epoch"] == m1["store"]["epoch"] + 1
    for m in (m1, m2):
        m["store"].pop("epoch")
        m["store"].pop("seq")
    assert m1 == m2
    outs = []
    for e in (eng, back):
        e.flush()
        e.decode_closed_loop(4)
        e.decode_step({"a": u[500]})
        outs.append(e.collect_decoded().tokens)
    assert set(outs[0]) == set(outs[1])
    for sid in outs[0]:
        np.testing.assert_array_equal(outs[0][sid], outs[1][sid],
                                      err_msg=str(sid))

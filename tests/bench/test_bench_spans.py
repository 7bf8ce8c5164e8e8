"""The reduction of the program's ``serve.*`` spans (``bench.spans``): on
hand-made intervals with known answers, on the program's own spans traced
on the CPU, and on the small trace recorded on one TPU v5e; and the harness's
own reduction of that trace, pinned as it stood before the program had
spans."""
import argparse
import asyncio
import shutil
from pathlib import Path

import jax
import pytest

import bench_testkit
from bench import spans as sp
from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "v5e_small.xplane.pb"
MS = 1_000_000                                  # one millisecond in ns


def _s(name, a, b, **attrs):
    return (name, a * MS, b * MS, attrs)


# Device busy [0,10], [30,35], [60,70] ms of a 100 ms window; idle [10,30],
# [35,60], [70,100].  Cycle 1 flushes a wave of requests 7 and 8 and routes
# 4 tokens; the loop waits while request 9 arrives; cycle 2 does nothing;
# cycle 3 admits request 9 and decodes and routes 8 tokens.
DEVICES = [{"XLA Ops": [("k", 0, 10 * MS), ("k", 30 * MS, 35 * MS),
                        ("k", 60 * MS, 70 * MS)]}]
SPANS = [
    _s("serve.submit", 1, 2, sid=7), _s("serve.submit", 2, 3, sid=8),
    _s("serve.cycle", 5, 40, live=0, queued=2),
    _s("serve.flush", 6, 20), _s("serve.plan", 6, 8),
    _s("serve.wave", 8, 20, rows=2, t_bucket=64, sids="7|8"),
    _s("serve.dispatch", 9, 12, program="prefill_wave"),
    _s("serve.block", 12, 18),
    _s("serve.route", 20, 40, tokens=4, sessions=2, unready=0),
    _s("serve.collect", 20, 22),
    _s("serve.wait", 40, 55), _s("serve.submit", 50, 52, sid=9),
    _s("serve.cycle", 55, 65, live=3, queued=1),
    _s("serve.cycle", 65, 90, live=3, queued=1),
    _s("serve.flush", 65, 66),
    _s("serve.wave", 65, 66, rows=1, t_bucket=32, sids=9),
    _s("serve.decode", 66, 80, rows=1, tokens=8),
    _s("serve.dispatch", 66, 70, program="closed_loop_fused"),
    _s("serve.route", 80, 88, tokens=8, sessions=1, unready=1),
]


def test_reduce_on_hand_made_spans():
    out = sp.reduce(DEVICES, (0, 100 * MS), SPANS)
    assert out["window_s"] == pytest.approx(0.1)
    # Idle under each innermost span; [90,100] is under none.
    want = {"serve.route": 21, "serve.wait": 13, "serve.decode": 10,
            "none": 10, "serve.cycle": 7, "serve.block": 6,
            "serve.dispatch": 2, "serve.wave": 2, "serve.collect": 2,
            "serve.submit": 2}
    got = dict(out["idle_by_span"])
    assert got == pytest.approx({k: v * 1e-3 for k, v in want.items()})
    assert [n for n, _ in out["idle_by_span"][:2]] == ["serve.route",
                                                       "serve.wait"]
    assert out["idle_none_share"] == pytest.approx(100 * 10 / 75)
    # Idle inside cycles: 25 + 5 + 20 ms of 100.
    assert out["idle_in_loop_share"] == pytest.approx(50.0)
    # Cycles 1 (35 ms) and 3 (25 ms) did work; cycle 2 did not.
    assert out["cycle_p95_ms"] == pytest.approx(25 + 0.95 * 10)
    assert out["route_us_per_token"] == pytest.approx(28_000 / 12)
    assert out["host_block_share"] == pytest.approx(100 * 6 / 70)
    # Waits 6, 5 and 13 ms.
    assert out["queue_wait_p50_ms"] == pytest.approx(6.0)
    # Self time: every instant of the window under exactly one name.
    assert sum(out["self_s"].values()) == pytest.approx(0.1)
    assert out["self_s"]["none"] == pytest.approx(0.013)
    assert out["self_s"]["serve.cycle"] == pytest.approx(0.013)
    assert out["count"]["serve.cycle"] == 3
    assert out["count"]["serve.dispatch"] == 2


def test_devices_average_and_no_device_plane():
    one = sp.reduce(DEVICES, (0, 100 * MS), SPANS)
    two = sp.reduce(DEVICES * 2, (0, 100 * MS), SPANS)
    assert dict(two["idle_by_span"]) == pytest.approx(
        dict(one["idle_by_span"]))
    assert two["idle_in_loop_share"] == pytest.approx(50.0)
    host = sp.reduce([], (0, 100 * MS), SPANS)
    assert host["idle_by_span"] == [] and host["idle_in_loop_share"] is None
    assert host["cycle_p95_ms"] == one["cycle_p95_ms"]


def test_innermost_prefers_the_latest_start():
    pieces = sp.innermost([_s("a", 0, 10), _s("b", 2, 4), _s("c", 2, 3)],
                          0, 12 * MS)
    assert [(a // MS, b // MS, n) for a, b, n in pieces] == [
        (0, 2, "a"), (2, 3, "c"), (3, 4, "b"), (4, 10, "a"),
        (10, 12, "none")]


def test_harness_reduction_of_the_recorded_trace_is_unchanged():
    out = tr.summarize(*tr.load(RECORDED))
    assert out["window_s"] == 0.068426971
    assert out["busy_s"] == 0.00078056
    assert out["breakdown"] == {"device_ops": [
        ["closed_loop_fused/%closed_loop_fused.1 kernel",
         0.00030369800000000003],
        ["prefill_wave/%prefill_wave.1 kernel", 0.00021135200000000002],
        ["prefill_wave/%copy.2", 1.9166000000000003e-05],
        ["prefill_wave/%copy.5", 1.7743000000000003e-05],
        ["prefill_wave/%copy.6", 1.6107e-05],
        ["prefill_wave/%slice_bitcast_fusion", 1.5583e-05],
        ["prefill_wave/%slice.35", 9.977000000000001e-06],
        ["prefill_wave/%fusion.4", 9.882000000000001e-06],
        ["_unstack/%fusion", 9.078e-06],
        ["prefill_wave/%copy.10", 7.974e-06]], "idle_gaps": [
        ["unannotated", 0.021965268000000003],
        ["unannotated", 0.0026233930000000003],
        ["unannotated", 0.0025261430000000002],
        ["unannotated", 0.0021250500000000003],
        ["unannotated", 0.002079846],
        ["engine.flush", 0.001648551], ["engine.flush", 0.00139334],
        ["engine.decode_closed_loop", 0.001304033],
        ["engine.decode_closed_loop", 0.001273262],
        ["engine.decode_closed_loop", 0.0012571210000000001]]}


def test_reduce_dir_adds_the_program_to_the_harness_summary(tmp_path):
    shutil.copy(RECORDED, tmp_path / "t.xplane.pb")
    got = sp.reduce_dir(tmp_path)
    want = tr.summarize(*tr.load(RECORDED))
    idle = got["breakdown"].pop("idle_by_span")
    program = got.pop("program")
    assert got == want
    # Recorded before the program had spans: all its idle is under none.
    assert idle == [["none", pytest.approx(want["window_s"]
                                           - want["busy_s"])]]
    assert program["idle_none_share"] == pytest.approx(100.0)
    assert program["cycle_p95_ms"] is None
    assert program["queue_wait_p50_ms"] is None


def test_reduce_reads_the_programs_own_spans(tmp_path):
    """A tiny open-loop session traced on the CPU (no device plane): the
    host-side quantities read what the program's spans carry."""
    from jax.profiler import TraceAnnotation

    from repro.core.esn import ESNConfig, LinearESN
    from repro.data.signals import mso_series
    from repro.serve import OpenLoopServer, ReservoirEngine
    cfg = ESNConfig(n=32, d_in=1, d_out=1, spectral_radius=0.9, leak=0.85,
                    ridge_alpha=1e-6, seed=9)
    sig = mso_series(3, 601)
    u, y = sig[:-1, None], sig[1:, None]
    model = LinearESN.diagonalized(cfg).fit(u[:400], y[:400], washout=50)

    async def run():
        server = OpenLoopServer(ReservoirEngine(model, max_slots=2,
                                                decode_wave_tokens=4))
        await server.start()
        handles = [await server.submit(i, u[8 * i:8 * i + 24], n_decode=6)
                   for i in range(3)]
        toks = [await h.tokens() for h in handles]
        await server.drain()
        return sum(len(t) for t in toks)

    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation(tr.WINDOW):
            served = asyncio.run(run())
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    devices, harness = tr.load(path)
    assert devices == []
    window = next((a, b) for n, a, b in harness if n == tr.WINDOW)
    spans = sp.load(path)
    out = sp.reduce(devices, window, spans)
    routed = sum(s[3]["tokens"] for s in spans if s[0] == "serve.route")
    assert routed == served == 18
    assert out["cycle_p95_ms"] > 0 and out["route_us_per_token"] > 0
    assert 0 <= out["host_block_share"] <= 100
    assert out["queue_wait_p50_ms"] >= 0
    assert out["idle_in_loop_share"] is None and out["idle_by_span"] == []


def test_the_script_times_cycles_and_restores_the_harness(tmp_path):
    import os

    from repro.serve.frontend import OpenLoopServer
    root = bench_testkit.make_root(tmp_path)
    cycle, reduce_dir = OpenLoopServer._cycle, tr.reduce_dir
    args = argparse.Namespace(workload="tiny.forecast", seconds=2.0,
                              trace=0)
    # The run turns on the compile cache in its checkout; the rest of the
    # test process keeps its own settings.
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    try:
        line = sp.run_seed(args, 2**33 + 5, root=root, require_tpu=False)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        if env is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env
    assert OpenLoopServer._cycle is cycle and tr.reduce_dir is reduce_dir
    assert line["correct"] and line["itl_p95_ms"] > 0
    assert line["stretches"]["all"]["cycle_host_p95_ms"] > 0
    assert "program" not in line

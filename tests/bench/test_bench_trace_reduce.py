"""The reduction from a profiler trace to busy and idle time, op and
program times, and the gap attribution of ``breakdown``: on hand-made
intervals, and on a small trace recorded on one TPU v5e."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_gaps():
    merged = tr.union([(5, 8), (0, 2), (1, 3), (7, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert tr.gaps(merged, -1, 12) == [(-1, 0), (3, 5), (9, 12)]


def test_summary_on_hand_made_planes():
    s = 1_000_000_000                    # one second in ns
    devices = [{
        "XLA Ops": [("kern_a", 0, 2 * s), ("kern_b", s, 3 * s),
                    ("kern_a", 6 * s, 7 * s), ("kern_c", 11 * s, 12 * s)],
        "XLA Modules": [("jit_step", 0, 3 * s), ("jit_step", 6 * s, 7 * s)],
    }]
    spans = [("bench.window", 0, 10 * s), ("engine.flush", 3 * s, 5 * s),
             ("engine.collect_decoded", 5 * s, 6 * s),
             ("engine.flush", 7 * s, 8 * s)]
    out = tr.summarize(devices, spans)
    assert out["window_s"] == 10.0
    assert out["busy_s"] == 4.0            # [0,3] and [6,7]; kern_c is out
    assert out["ops_s"] == {"kern_a": 3.0, "kern_b": 2.0}
    assert out["modules_s"] == {"jit_step": 4.0}
    assert out["breakdown"]["device_ops"] == [["kern_a", 3.0],
                                              ["kern_b", 2.0]]
    # Gap [3,6]: flush covers 2 s of it, more than collect's 1 s.  Gap
    # [7,10]: flush covers 1 s, under half of it.
    assert out["breakdown"]["idle_gaps"] == [["engine.flush", 3.0],
                                             ["unannotated", 3.0]]


def test_devices_average_and_unannotated_gaps():
    s = 1_000_000_000
    devices = [{"XLA Ops": [("k", 0, 4 * s)]}, {"XLA Ops": [("k", 0, 2 * s)]}]
    out = tr.summarize(devices, [("bench.window", 0, 4 * s)])
    assert out["busy_s"] == 3.0
    assert out["breakdown"]["idle_gaps"] == [["unannotated", 2.0]]


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize([{"XLA Ops": []}], [("engine.flush", 0, 1)])


# A trace recorded on one TPU v5e: one prefill wave of 4 rows x 600 steps
# (the Pallas scan), three fused decode waves of K=8 over them, inside a
# ``bench.window`` span, with the harness's host spans around the calls.
RECORDED = DATA / "v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return tr.load(RECORDED)


def test_recorded_trace_window_and_busy_time(recorded):
    devices, spans = recorded
    out = tr.summarize(devices, spans)
    assert len(devices) == 1                      # one chip
    assert out["window_s"] == pytest.approx(0.068426971, abs=1e-9)
    assert out["busy_s"] == pytest.approx(0.00078056, abs=1e-9)
    # An independent sweep over the raw events gives the same union.
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(RECORDED))
    w0, w1 = next((e.start_ns, e.end_ns) for p in data.planes
                  for line in p.lines for e in line.events
                  if e.name == "bench.window")
    edges = []
    for p in data.planes:
        for line in p.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    a, b = max(e.start_ns, w0), min(e.end_ns, w1)
                    if b > a:
                        edges += [(a, 1), (b, -1)]
    depth, busy, last = 0, 0, None
    for t, step in sorted(edges, key=lambda x: (x[0], -x[1])):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert out["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)


def test_recorded_trace_kernels_programs_and_gaps(recorded):
    out = tr.summarize(*recorded)
    mods, ops = out["modules_s"], out["ops_s"]
    decode = ops["closed_loop_fused/%closed_loop_fused.1 kernel"]
    scan = ops["prefill_wave/%prefill_wave.1 kernel"]
    assert 0 < decode <= mods["closed_loop_fused"]
    assert 0 < scan <= mods["prefill_wave"]
    kernels = [k for k in ops if k.endswith(" kernel")]
    assert sorted(kernels) == sorted([
        "closed_loop_fused/%closed_loop_fused.1 kernel",
        "prefill_wave/%prefill_wave.1 kernel"])
    # Busy time plus every idle gap is the window.
    merged = tr.union((a, b) for lines in recorded[0]
                      for _, a, b in tr.clip(lines["XLA Ops"],
                                             *_window(recorded)))
    idle = tr.gaps(merged, *_window(recorded))
    assert (sum(b - a for a, b in idle) * 1e-9 + out["busy_s"]
            == pytest.approx(out["window_s"], rel=1e-9))
    names = {n for n, _ in out["breakdown"]["idle_gaps"]}
    assert names <= {"unannotated", "engine.flush", "engine.decode_closed_loop",
                     "engine.collect_decoded"}
    assert "engine.decode_closed_loop" in names
    assert len(out["breakdown"]["device_ops"]) == 10


def _window(recorded):
    return next((a, b) for n, a, b in recorded[1] if n == "bench.window")

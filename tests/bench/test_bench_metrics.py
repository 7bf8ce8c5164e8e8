"""Metric arithmetic on hand-made records: latency counts from the due time,
failures count to the cut, and the engine's shares."""
from pathlib import Path

import numpy as np
import pytest

from bench.client import Served
from bench.spec import Spec

SPEC = Spec(Path(__file__).resolve().parents[2])


def read(name, rec):
    return SPEC.reader(name)(rec)


def _rec(requests, events=(), cut=115.0):
    return {"window": (100.0, 110.0), "seconds": 10.0,
            "cut": cut, "requests": list(requests), "events": list(events),
            "max_slots": 8, "compiles": [], "setup_s": 7.5, "trace": None}


def _served(due, submit, recv, horizon=None, length=10):
    return Served(index=0, length=length,
                  horizon=len(recv) if horizon is None else horizon,
                  due=due, submit=submit, recv=list(recv))


def test_ttft_counts_from_due_not_from_send():
    # Sent 40 ms late, first token 10 ms after the send: 50 ms from due.
    rec = _rec([_served(101.0, 101.04, [101.05, 101.06])] * 20)
    assert read("ttft_p95_ms", rec) == pytest.approx(50.0)
    assert read("gen_late_p95_ms", rec) == pytest.approx(40.0)


def test_ttft_p95_is_over_every_request_and_failures_count_to_the_cut():
    reqs = [_served(100.0 + i * 0.1, 100.0 + i * 0.1,
                    [100.0 + i * 0.1 + 0.001 * (i + 1)]) for i in range(19)]
    reqs.append(_served(101.0, 101.0, [], horizon=4))       # never answered
    got = read("ttft_p95_ms", _rec(reqs))
    ttfts = [1.0 * (i + 1) for i in range(19)] + [(115.0 - 101.0) * 1e3]
    assert got == pytest.approx(np.percentile(ttfts, 95))


def test_a_traced_stretch_counts_failures_to_the_harness_cut():
    """A traced run's metrics read the traced stretch, but the harness
    waited until the end of all the measured seconds plus grace."""
    reqs = [_served(101.0, 101.0, [101.01])] * 19
    reqs.append(_served(101.0, 101.0, [], horizon=4))       # never answered
    got = read("ttft_p95_ms", _rec(reqs, cut=180.0))
    assert got == pytest.approx(np.percentile([10.0] * 19 + [79000.0], 95))


def test_ttft_median_counts_from_due_and_failures_to_the_cut():
    reqs = [_served(100.0, 100.02, [100.0 + 0.01 * (i + 1)])
            for i in range(4)]
    reqs.append(_served(101.0, 101.0, [], horizon=4))       # never answered
    got = read("ttft_p50_ms", _rec(reqs))
    assert got == pytest.approx(np.median([10, 20, 30, 40, 14000.0]))


def test_itl_gaps_of_all_requests():
    a = _served(100.0, 100.0, [100.1, 100.1, 100.3])          # gaps 0, 0.2
    b = _served(101.0, 101.0, [101.5, 101.6], horizon=3)      # cut: 0.1, 13.4
    got = read("itl_p95_ms", _rec([a, b]))
    assert got == pytest.approx(np.percentile(
        [0.0, 200.0, 100.0, (115.0 - 101.6) * 1e3], 95))


def test_live_share():
    ev = [{"t": 101.0, "kind": "decode", "rows": 2, "tokens": 8,
           "t_bucket": None},
          {"t": 102.0, "kind": "decode", "rows": 6, "tokens": 3,
           "t_bucket": None},
          {"t": 99.0, "kind": "decode", "rows": 8, "tokens": 8,
           "t_bucket": None},                     # before the window
          {"t": 103.0, "kind": "prefill", "rows": 3, "tokens": 1000,
           "t_bucket": 1024}]
    rec = _rec([], ev)
    assert read("decode_live_share", rec) == pytest.approx(100 * 8 / 16)


def test_trace_metrics_are_silent_without_a_trace():
    rec = _rec([])
    for name in ("device_idle_share.forecast", "decode_kernel_roofline",
                 "mfu.forecast"):
        assert read(name, rec) is None


def test_trace_metrics_on_the_recorded_trace():
    """The recorded v5e trace's work: one prefill wave of 4 rows x 600
    steps, three K=8 decode waves of 4 rows, at MSO-N1024's shapes."""
    import json

    from bench import flops_bytes, trace_reduce
    root = Path(__file__).resolve().parents[2]
    trace = trace_reduce.summarize(*trace_reduce.load(
        Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"))
    cfg = json.loads((root / "bench/configs/mso-n1024.json").read_text())
    ev = ([{"t": 101.0, "kind": "prefill", "rows": 4, "tokens": 2400,
            "t_bucket": 1024}]
          + [{"t": 102.0 + i, "kind": "decode", "rows": 4, "tokens": 8,
              "t_bucket": None} for i in range(3)])
    rec = {**_rec([], ev), "trace": trace,
           "model": flops_bytes.shapes(cfg["model"]),
           "peaks": flops_bytes.peaks("TPU v5 lite", root)}
    for name in ("decode_kernel_roofline", "mfu.forecast"):
        v = read(name, rec)
        assert 0 < v < 100, (name, v)
    idle = read("device_idle_share.forecast", rec)
    assert idle == pytest.approx(100 * (1 - trace["busy_s"]
                                        / trace["window_s"]))

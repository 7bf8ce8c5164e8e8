"""A configuration, a cell and a per-layer metric added as new files and
entries, in a temporary copy of the benchmark: the harness finds and runs
them with no edit to its code."""
import json

import pytest

import bench_testkit
from bench.spec import Spec

READER = '''"""Decode waves in the window (a metric a later change adds)."""
from bench.reduce import in_window


def read(rec):
    return len(in_window(rec, "decode")) or None
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_testkit.make_root(tmp_path_factory.mktemp("bench"))
    (root / "bench/metrics/decode_waves.py").write_text(READER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "decode_waves", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "itl_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_the_harness_finds_the_new_entries(root):
    spec = Spec(root)
    assert spec.workload("tiny.forecast")["config"] == "tiny"
    assert spec.config("tiny")["model"]["n"] == 64
    e2e = {m["name"] for m in spec.metrics("tiny.forecast", trace=False)}
    assert e2e == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    layer = {m["name"] for m in spec.metrics("tiny.forecast", trace=True)}
    # A metric without "workloads" goes to every cell that reports the
    # end-to-end metric it moves.
    assert "decode_waves" in layer and "gen_late_p95_ms" in layer


def test_a_traced_run_of_the_new_cell_reports_the_new_metric(root):
    out = bench_testkit.run(root, "tiny.forecast", trace=1)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    assert got["decode_waves"]["value"] > 0
    assert got["decode_waves"]["unit"] == "count"
    assert 0 < got["decode_live_share"]["value"] <= 100
    # No device plane on the CPU: the trace's metrics stay silent.
    assert "mfu.forecast" not in got and "breakdown" not in out


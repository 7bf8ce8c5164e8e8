"""The Kuramoto-Sivashinsky configuration and the cells added with it: the
integrator holds the field's invariants, the engine agrees with the float64
reference on a tiny KS-shaped cell (D>1 closed loop), the configuration's
fitted loop stays bounded, and the control fails each new cell's limit."""
import json
from pathlib import Path

import numpy as np
import pytest

import bench_testkit
from bench import model as model_mod, reference, run as run_mod
from bench.signals import ks
from bench.spec import Spec
from test_bench_reference import (
    test_control_fails_the_cell_limit as _control_fails)

ROOT = Path(__file__).resolve().parents[2]
KS = {"L": 22, "q": 64, "dt": 0.25}


@pytest.fixture(scope="module")
def field():
    return ks.generate(np.random.default_rng(5), 4000, **KS)


def test_the_same_seed_gives_the_same_field():
    a = ks.generate(np.random.default_rng(2**33 + 1), 50, **KS)
    b = ks.generate(np.random.default_rng(2**33 + 1), 50, **KS)
    c = ks.generate(np.random.default_rng(2**33 + 2), 50, **KS)
    assert a.shape == (50, 64) and a.dtype == np.float64
    assert np.array_equal(a, b) and not np.allclose(a, c)


def test_the_spatial_mean_stays_zero(field):
    assert np.abs(field.mean(axis=1)).max() < 1e-10


def test_the_field_stays_bounded(field):
    assert np.isfinite(field).all()
    assert np.abs(field).max() < 5.0
    assert 0.8 < field.std() < 1.6          # on the attractor, not decayed


def test_the_power_peaks_at_the_most_unstable_mode(field):
    """``k^2 - k^4`` grows fastest at ``k = 1/sqrt(2)``; at L=22 the modes
    ``k = 2 pi m / 22`` nearest it are m=2 and m=3."""
    power = (np.abs(np.fft.rfft(field, axis=1)) ** 2).mean(axis=0)
    nearest = np.argsort(np.abs(2 * np.pi * np.arange(33) / 22
                                - 2 ** -0.5))[:2]
    assert set(nearest) == {2, 3}
    assert int(np.argmax(power)) in (2, 3)


def _add_tiny_ks(root: Path) -> str:
    """A tiny KS-shaped configuration and its cell, added to the checkout as
    ``bench_testkit.make_root`` adds its tiny MSO cell: N=64, a field of 8
    points.  At 8 points the domain is cut to L=12, which 8 points resolve
    (a travelling wave); at L=22 they alias and the integration diverges."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-ks", "source": "test",
                             "file": "bench/configs/tiny-ks.json",
                             "reduced": ["n", "signal"], "why": "tiny"})
    cfg = json.loads((ROOT / "bench/configs/ks22-n5000.json").read_text())
    cfg["model"].update(n=64, d_in=8, d_out=8)
    cfg["signal"].update(L=12, q=8)
    cfg["fit"]["train_steps"] = 600
    cfg["signal_steps"] = 4000
    cfg["engine"].update(max_slots=8, max_wave=2)
    (root / "bench/configs/tiny-ks.json").write_text(json.dumps(cfg))
    cell, base = "tiny-ks.forecast", "ks22-n5000.forecast"
    bench["workloads"].append({"name": cell, "config": "tiny-ks",
                               "traffic": "forecast", "chips": 1,
                               "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if base in m.get("workloads", ()):
            m["workloads"].append(cell)
    wl = json.loads((ROOT / f"bench/workloads/{base}.json").read_text())
    wl.update(config="tiny-ks", run_in_s=0.5, grace_s=30.0,
              check_requests=4, limits={"out_gap": 1e-4})
    wl["traffic"].update(rate=6.0, prompt={"xm": 16, "alpha": 1.3, "cap": 40},
                         horizon={"lo": 8, "hi": 20})
    (root / f"bench/workloads/{cell}.json").write_text(json.dumps(wl))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


def test_engine_agrees_with_the_reference_on_a_wide_signal(tmp_path):
    root = bench_testkit.make_root(tmp_path)
    out = bench_testkit.run(root, _add_tiny_ks(root))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["out_gap"]["value"] < 1e-4
    assert out["checks"]["loop_growth"]["value"] <= 100.0


def test_engine_agrees_with_the_reference_through_chunked_prefill(tmp_path):
    """The ingest cell's path at a tiny size: prompts longer than the
    workload's ``chunk_max`` reach the arena in chunks."""
    root = bench_testkit.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = "tiny.ingest"
    bench["workloads"].append({"name": cell, "config": "tiny",
                               "traffic": "ingest", "chips": 1,
                               "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "mso-n1024.ingest" in m.get("workloads", ()):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    wl = json.loads((ROOT / "bench/workloads/mso-n1024.ingest.json"
                     ).read_text())
    wl.update(config="tiny", run_in_s=0.5, check_requests=4,
              engine={"max_slots": 8, "chunk_max": 32},
              limits={"out_gap": 1e-4})
    wl["traffic"].update(rate=6.0, prompt={"xm": 40, "alpha": 1.5,
                                           "cap": 160},
                         horizon={"lo": 4, "hi": 8})
    (root / f"bench/workloads/{cell}.json").write_text(json.dumps(wl))
    chunked = []

    def spy(engine):
        chunked.append(engine.scheduler.chunk_max)
    out = bench_testkit.run(root, cell, fault=spy)
    assert chunked == [32]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p50_ms", "setup_s"}


@pytest.fixture(scope="module")
def ks_model():
    cfg = json.loads((ROOT / "bench/configs/ks22-n5000.json").read_text())
    model, sig = model_mod.build(cfg, 2**31 + 77)
    return cfg, model, sig


def test_the_configuration_is_the_published_one(ks_model):
    cfg, model, sig = ks_model
    assert model.n == 5000 and model.n_real == 56
    assert model.lam.shape == (2528,) and model.w_in.shape == (64, 2528)
    assert model.w_out.shape == (5001, 64)
    assert sig.shape == (cfg["signal_steps"], 64)
    assert cfg["reduced"] == []


def test_the_fitted_loop_stays_bounded_over_the_longest_horizon(ks_model):
    cfg, model, _ = ks_model
    wl = json.loads((ROOT / "bench/workloads/ks22-n5000.forecast.json"
                     ).read_text())
    growth = model_mod.closed_loop_growth(
        model, wl["traffic"]["horizon"]["hi"], np.random.default_rng(3))
    assert growth <= cfg["loop_growth_max"]


def test_control_fails_the_ks_cell_limit(ks_model):
    """As ``test_control_fails_the_cell_limit``, at N=5000, D=64: the
    longest prompt and horizon of the cell, and a short one."""
    _, model, sig = ks_model
    wl = json.loads((ROOT / "bench/workloads/ks22-n5000.forecast.json"
                     ).read_text())
    tr = wl["traffic"]
    lengths = [tr["prompt"]["cap"], tr["prompt"]["xm"]]
    horizons = [tr["horizon"]["hi"], tr["horizon"]["lo"]]
    prompts = [sig[100:100 + n] for n in lengths]
    want = reference.reference_outputs(model, prompts, horizons)
    got = reference.control_outputs(model, prompts, horizons)
    assert reference.gap(got, want) > wl["limits"]["out_gap"]


def test_control_fails_the_ingest_cell_limit():
    _control_fails("mso-n1024.ingest")


def test_the_harness_finds_the_new_cells():
    spec = Spec(ROOT)
    ks_cell = spec.workload("ks22-n5000.forecast")
    assert spec.config(ks_cell["config"])["model"]["d_in"] == 64
    ingest = spec.workload("mso-n1024.ingest")
    assert ingest["chips"] == ks_cell["chips"] == 1

    def names(cell, trace):
        return {m["name"] for m in spec.metrics(cell, trace=trace)}
    assert names("ks22-n5000.forecast", False) == {
        "ttft_p50_ms", "itl_p95_ms", "setup_s"}
    assert names("mso-n1024.ingest", False) == {"ttft_p50_ms", "setup_s"}
    assert {"decode_kernel_roofline", "mfu.forecast",
            "device_idle_share.forecast"} <= names("ks22-n5000.forecast",
                                                   True)
    assert names("mso-n1024.ingest", True) == {"gen_late_p95_ms",
                                               "ttft_p95_ms"}


def test_the_ingest_cell_warms_every_chunk_bucket():
    """Prompts of 2048-32768 steps reach the prefill only as 1024-step
    chunks and their remainders: every bucket up to 1024, none above."""
    spec = Spec(ROOT)
    wl = spec.workload("mso-n1024.ingest")
    cfg = spec.config(wl["config"])
    eng = {**cfg["engine"], **wl["engine"]}
    assert eng["max_slots"] == 128 and eng["chunk_max"] == 1024
    lengths = run_mod.wave_lengths(wl["traffic"], eng)
    assert lengths[-1] == 1024 and lengths[0] <= eng["bucket_min"]

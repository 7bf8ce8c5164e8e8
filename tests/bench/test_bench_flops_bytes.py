"""The least operations and bytes of the benchmark's counts, against hand
counts for the configuration and for a wide-drive reservoir."""
import json
from pathlib import Path

from bench import flops_bytes as fb

ROOT = Path(__file__).resolve().parents[2]


def _shapes(name):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    return fb.shapes(cfg["model"])


def test_mso_counts():
    s = _shapes("mso-n1024")
    # N=1024: 26 real eigenvalues (round(sqrt(2048/pi))), 499 pairs, D=1.
    assert s == {"n": 1024, "n_real": 26, "n_pair": 499, "d_in": 1,
                 "d_out": 1}
    assert fb.recurrence_flops(s) == 2 * 26 + 8 * 499 == 4044
    assert fb.step_flops(s) == 4044 + 2 * 1024 == 6092
    assert fb.readout_flops(s) == 2 * 1025 == 2050
    assert fb.token_flops(s) == 8142
    weights = 4 * (1024 + 1024 + 1025)
    assert fb.weight_bytes(s) == weights == 12292
    # 3 live rows, 8 tokens: states and last outputs read and written.
    assert fb.decode_call(s, 3, 8) == (3 * 8 * 8142,
                                       weights + 3 * 4 * 2 * 1025 + 96)


def test_ks_counts():
    # The Kuramoto-Sivashinsky forecaster of Pathak et al. (2017) at Q=64:
    # N=5000, 56 real eigenvalues, 2472 pairs, D=64.
    s = fb.shapes({"n": 5000, "d_in": 64, "d_out": 64})
    assert s == {"n": 5000, "n_real": 56, "n_pair": 2472, "d_in": 64,
                 "d_out": 64}
    assert fb.recurrence_flops(s) == 112 + 19776 == 19888
    assert fb.step_flops(s) == 19888 + 2 * 64 * 5000 == 659888
    assert fb.readout_flops(s) == 2 * 5001 * 64 == 640128
    # About 1.3 MFLOP a token, mostly the 64-wide drive and readout.
    assert fb.token_flops(s) == 1300016
    weights = 4 * (5000 + 64 * 5000 + 5001 * 64)
    assert fb.decode_call(s, 1, 1) == (1300016,
                                       weights + 4 * 2 * 5064 + 4 * 64)


def test_least_seconds_takes_the_binding_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert fb.least_seconds(1000, 50, peak) == 10.0      # compute-bound
    assert fb.least_seconds(100, 500, peak) == 50.0      # memory-bound


def test_peaks_keyed_by_device_kind():
    import pytest
    v5e = fb.peaks("TPU v5 lite", ROOT)
    assert v5e == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        fb.peaks("TPU v9 imaginary", ROOT)

"""The traffic generator: every seed gets the same multiset of sizes and
gaps, in another order, within the workload's bounds."""
import numpy as np

from bench import loadgen

TRAFFIC = {"rate": 40.0, "prompt": {"xm": 64, "alpha": 1.3, "cap": 2048},
           "horizon": {"lo": 128, "hi": 1024}}


def _open(seed):
    return loadgen.open_loop(np.random.default_rng(seed), TRAFFIC,
                             run_in=2.0, seconds=10.0, tail=3.0,
                             signal_len=40000)


def test_seeds_permute_one_multiset_in_every_block():
    a, b = _open(1), _open(2**33 + 1)
    assert len(a) == len(b) == int(np.ceil(40.0 * 15.0))
    block = loadgen.BLOCK
    for field in ("length", "horizon"):
        va = [getattr(r, field) for r in a]
        vb = [getattr(r, field) for r in b]
        assert va != vb
        for i in range(0, len(a) - block + 1, block):
            assert sorted(va[i:i + block]) == sorted(vb[i:i + block])
    gaps_a = np.diff(np.r_[-2.0, [r.due for r in a]])
    gaps_b = np.diff(np.r_[-2.0, [r.due for r in b]])
    for i in range(0, len(a) - block + 1, block):
        assert np.allclose(np.sort(gaps_a[i:i + block]),
                           np.sort(gaps_b[i:i + block]))


def test_bounds_and_rates():
    reqs = _open(7)
    lengths = np.array([r.length for r in reqs])
    horizons = np.array([r.horizon for r in reqs])
    assert lengths.min() == 64 and lengths.max() == 2048
    assert 128 <= horizons.min() and horizons.max() <= 1024
    assert reqs[0].due > -2.0 and abs(reqs[-1].due - 13.0) < 1.0
    # Bounded Pareto, alpha 1.3: the median is xm 2^(1/alpha) ~ 1.7 xm.
    assert 100 < np.median(lengths) < 120
    assert all(r.offset + r.length < 40000 for r in reqs)

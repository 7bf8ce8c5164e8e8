"""The comparison that decides ``correct``: the engine agrees with the
float64 reference, and the control and the planted faults do not."""
import json
from pathlib import Path

import numpy as np
import pytest

import bench_testkit
from bench import model as model_mod, reference

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_testkit.make_root(tmp_path_factory.mktemp("bench"))


def test_engine_agrees_with_the_reference(root):
    out = bench_testkit.run(root, "tiny.forecast")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["out_gap"]["value"] < 1e-4
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", ["mso-n1024.forecast"])
def test_control_fails_the_cell_limit(cell):
    """The reference at ``high`` precision (three bfloat16 passes) in place
    of the program, at the configuration's widths, on the traffic's longest
    prompt (at most 8192 steps, to fit a test run) and horizon and a short
    one: its gap passes the cell's limit."""
    wl = json.loads((ROOT / f"bench/workloads/{cell}.json").read_text())
    cfg = json.loads((ROOT / f"bench/configs/{wl['config']}.json").read_text())
    cfg["signal_steps"] = max(cfg["fit"]["train_steps"] + 1, 8192 + 200)
    model, sig = model_mod.build(cfg, 11)
    tr = wl["traffic"]
    lengths = [min(tr["prompt"]["cap"], 8192), tr["prompt"]["xm"]]
    horizons = [tr["horizon"]["hi"], tr["horizon"]["lo"]]
    prompts = [sig[100:100 + n] for n in lengths]
    want = reference.reference_outputs(model, prompts, horizons)
    got = reference.control_outputs(model, prompts, horizons)
    assert reference.gap(got, want) > wl["limits"]["out_gap"]


def _wrap(engine, attr, fn):
    ex = engine._exec
    orig = getattr(ex, attr)
    setattr(ex, attr, lambda *a, **k: fn(orig, *a, **k))


def _decode_state_unchanged(engine):
    def fn(orig, params, w, arena, *rest):
        _, ys = orig(params, w, arena, *rest)
        return arena, ys
    _wrap(engine, "_closed_jit", fn)


def _token_altered(engine):
    def fn(orig, *args):
        arena, ys = orig(*args)
        return arena, ys.at[-1].add(0.01)
    _wrap(engine, "_closed_jit", fn)


def _half_wave_left_out(engine):
    def fn(orig, params, w, arena, slots, *rest, **kw):
        new, out = orig(params, w, arena, slots, *rest, **kw)
        skip = slots[::2]
        return type(new)(states=new.states.at[skip].set(arena.states[skip]),
                         y_prev=new.y_prev.at[skip].set(arena.y_prev[skip]),
                         active=new.active), out
    _wrap(engine, "_wave_jit", fn)


@pytest.mark.parametrize("fault", [_decode_state_unchanged, _token_altered,
                                   _half_wave_left_out])
def test_a_broken_timed_path_is_not_correct(root, fault):
    out = bench_testkit.run(root, "tiny.forecast", fault=fault)
    assert not out["correct"]
    assert out["checks"]["out_gap"]["value"] > out["checks"]["out_gap"][
        "limit"]


def test_reference_matches_a_plain_loop():
    rng = np.random.default_rng(0)
    nc, nr = 5, 2
    lam = np.concatenate([rng.uniform(-.9, .9, nr),
                          0.8 * np.exp(1j * rng.uniform(0, 3, nc - nr))])
    m = model_mod.Model(lam=lam.astype(complex),
                        w_in=rng.standard_normal((1, nc)) + 0j,
                        w_out=rng.standard_normal((1 + 8, 1)), n_real=nr)
    u = rng.standard_normal((7, 1))
    h = np.zeros(nc, complex)
    for t in range(7):
        h = lam * h + u[t] @ m.w_in
    ys = []
    y = m.w_out[0] + model_mod.packed(h, nr) @ m.w_out[1:]
    for _ in range(3):
        h = lam * h + y @ m.w_in
        y = m.w_out[0] + model_mod.packed(h, nr) @ m.w_out[1:]
        ys.append(y)
    got = reference.reference_outputs(m, [u, u[:3]], [3, 2])
    np.testing.assert_allclose(got[0], np.stack(ys), rtol=1e-12)
    assert got[1].shape == (2, 1)

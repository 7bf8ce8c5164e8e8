"""Fused K-token decode: one kernel dispatch == K single steps, everywhere.

The fused decode kernel (``kernels/diag_scan.decode_fused_pallas_raw`` +
``kernels/ref.decode_fused_ref``, routed by ``core.dispatch.run_decode_fused``)
folds diag step + readout matmul + ensemble reduce + feedback write into one
dispatch that runs K tokens.  These tests pin the contract that makes it safe
to thread K-token waves through the whole serving stack:

* a fused K-token wave is bit-parity (<= 1e-5) with K single ``decode_step``
  calls feeding their own outputs back;
* feedback seeds across wave boundaries — two K-waves == one 2K-wave ==
  2K single steps (state and ``y_prev`` carry exactly);
* ``ensemble="mean"`` fusion inside the kernel matches the pre-fusion
  ``arena.closed_loop`` scan path;
* an ``observe()`` teacher write landing between fused waves retargets the
  next wave's feedback;
* the reference backend and the Pallas kernel (interpret mode off-TPU)
  agree, including for feedback models where the two drive matmuls fold
  into one ``win_q + wfb_q``;
* every decode path drains through one typed :class:`DecodeResult` of host
  numpy arrays, each wave copied to the host once.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch as core_dispatch
from repro.core import esn as esn_fn
from repro.core.esn import ESNConfig
from repro.core.params import stack_params
from repro.data.signals import mso_series
from repro.serve import DecodeResult, ReservoirEngine
from repro.serve import arena as arena_mod

CFG = ESNConfig(n=48, d_in=1, d_out=1, spectral_radius=0.9, leak=0.8,
                input_scaling=0.5, ridge_alpha=1e-8, seed=7)


def _trained(cfg=CFG):
    sig = mso_series(3, 801)
    params = esn_fn.diag_params(cfg)
    readout = esn_fn.fit(params, sig[:-1, None], sig[1:, None], washout=50)
    return params, readout, sig


def _engine(params, readout, sig, sids, **kw):
    eng = ReservoirEngine(params, max_slots=max(4, len(sids)),
                          readout=readout, **kw)
    for i, s in enumerate(sids):
        eng.submit(s, sig[600 + i:700 + i, None])
    eng.flush()
    return eng


def _stepwise(eng, sids, n):
    """n closed-loop tokens via n single decode_step dispatches."""
    cur = {s: np.asarray(eng.arena.y_prev[eng.sessions[s].slot])
           for s in sids}
    out = {s: [] for s in sids}
    for _ in range(n):
        cur = eng.decode_step(cur)
        for s in sids:
            out[s].append(np.asarray(cur[s]))
    return {s: np.concatenate([r[None] if r.ndim == 1 else r for r in v])
            for s, v in out.items()}


# ---------------------------------------------------- K-wave == K steps
def test_fused_wave_matches_k_single_steps():
    params, readout, sig = _trained()
    sids = ["a", "b", "c"]
    fused = _engine(params, readout, sig, sids)
    ys = fused.decode_closed_loop(6)
    step = _engine(params, readout, sig, sids)
    ref = _stepwise(step, sids, 6)
    for s in sids:
        np.testing.assert_allclose(np.asarray(ys[s]).ravel(),
                                   ref[s].ravel(), atol=1e-5)


def test_feedback_seeds_across_wave_boundaries():
    """Two fused K-waves == one 2K-wave == 2K single steps: the feedback
    (y_prev) and slot state written by wave 1 are exactly what wave 2 reads."""
    params, readout, sig = _trained()
    sids = ["a", "b"]
    two2 = _engine(params, readout, sig, sids)
    w1 = two2.decode_closed_loop(4)
    w2 = two2.decode_closed_loop(4)
    pair = {s: np.concatenate([np.asarray(w1[s]), np.asarray(w2[s])])
            for s in sids}
    one = _engine(params, readout, sig, sids)
    whole = one.decode_closed_loop(8)
    step = _engine(params, readout, sig, sids)
    ref = _stepwise(step, sids, 8)
    for s in sids:
        np.testing.assert_allclose(pair[s].ravel(),
                                   np.asarray(whole[s]).ravel(), atol=1e-6)
        np.testing.assert_allclose(pair[s].ravel(), ref[s].ravel(),
                                   atol=1e-5)
    # the arena state after two waves matches the single-wave engine's
    np.testing.assert_allclose(np.asarray(two2.states),
                               np.asarray(one.states), atol=1e-6)


def test_partial_mask_freezes_inactive_rows():
    """Fused waves restricted to a sid subset must not move the other rows'
    state, feedback, or emit tokens for them."""
    params, readout, sig = _trained()
    sids = ["a", "b", "c"]
    eng = _engine(params, readout, sig, sids)
    slot_c = eng.sessions["c"].slot
    h_before = np.asarray(eng.arena.states[slot_c])
    y_before = np.asarray(eng.arena.y_prev[slot_c])
    ys = eng.decode_closed_loop(5, sids=["a", "b"])
    assert set(ys) == {"a", "b"}
    np.testing.assert_array_equal(np.asarray(eng.arena.states[slot_c]),
                                  h_before)
    np.testing.assert_array_equal(np.asarray(eng.arena.y_prev[slot_c]),
                                  y_before)
    step = _engine(params, readout, sig, sids)
    ref = _stepwise(step, ["a", "b"], 5)
    for s in ("a", "b"):
        np.testing.assert_allclose(np.asarray(ys[s]).ravel(),
                                   ref[s].ravel(), atol=1e-5)


# ------------------------------------------------- ensemble-mean fusion
def _batched_trained(n_members=3):
    """Param-batched members must share static aux (n_real) to stack."""
    sig = mso_series(3, 801)
    batch, seed = [], 0
    while len(batch) < n_members and seed < 60:
        seed += 1
        p = esn_fn.diag_params(dataclasses.replace(CFG, seed=seed))
        if not batch or p.n_real == batch[0].n_real:
            batch.append(p)
    assert len(batch) == n_members
    params = stack_params(batch)
    from repro.core.params import Readout
    readout = Readout(jnp.stack([
        esn_fn.fit(p, sig[:-1, None], sig[1:, None], washout=50).w_out
        for p in batch]))
    return params, readout, sig


def test_ensemble_mean_fused_matches_scan_path():
    params, readout, sig = _batched_trained()
    eng = ReservoirEngine.from_param_batch(params, readout=readout,
                                           ensemble="mean")
    for i in range(eng.max_slots):
        eng.submit(i, sig[600:700, None])
    eng.flush()
    arena0 = eng.arena
    mask = np.ones((eng.max_slots,), bool)
    _, ys_scan = arena_mod.closed_loop(params, readout.w_out, arena0, mask,
                                       7, batched=True, ensemble="mean")
    arena_f, ys_fused = arena_mod.closed_loop_fused(
        params, readout.w_out, arena0, mask, 7, batched=True,
        ensemble="mean")
    np.testing.assert_allclose(np.asarray(ys_fused), np.asarray(ys_scan),
                               atol=1e-5)
    ys = eng.decode_closed_loop(7)
    # every sid's series IS the fused mean series
    for i in range(1, eng.max_slots):
        np.testing.assert_array_equal(np.asarray(ys[0]), np.asarray(ys[i]))
    np.testing.assert_allclose(np.asarray(ys[0]), np.asarray(ys_scan)[:, 0],
                               atol=1e-5)


# ------------------------------------------ observe() between fused waves
def test_observe_teacher_write_lands_mid_wave():
    params, readout, sig = _trained()
    s = "chat"
    eng = _engine(params, readout, sig, [s])
    w1 = np.asarray(eng.decode_closed_loop(3)[s])
    y_star = np.asarray([1.5])                  # far from the model's output
    assert abs(float(w1[-1, 0]) - 1.5) > 1e-3
    eng.observe(s, y_star)
    w2 = np.asarray(eng.decode_closed_loop(3)[s])

    step = _engine(params, readout, sig, [s])
    ref1 = _stepwise(step, [s], 3)[s]
    np.testing.assert_allclose(w1.ravel(), ref1.ravel(), atol=1e-5)
    # the teacher value drives the next wave's FIRST step, then free-run
    cur = {s: y_star}
    ref2 = []
    for _ in range(3):
        cur = step.decode_step(cur)
        ref2.append(np.asarray(cur[s]))
    np.testing.assert_allclose(w2.ravel(),
                               np.concatenate(ref2).ravel(), atol=1e-5)


# --------------------------------------------- backend parity (dispatch)
@pytest.mark.parametrize("use_feedback", [False, True])
def test_ref_and_pallas_interpret_agree(use_feedback):
    cfg = dataclasses.replace(CFG, n=40, d_in=2, d_out=2,
                              use_feedback=use_feedback)
    params = esn_fn.diag_params(cfg)
    rng = np.random.default_rng(0)
    d = cfg.d_out
    n_feat = int(cfg.use_bias) + (d if use_feedback else 0) + cfg.n
    w_out = rng.normal(0, 0.1, (n_feat, d))
    w_drive = params.win_q + params.wfb_q if use_feedback else params.win_q
    states = rng.normal(0, 0.5, (3, cfg.n))
    y_prev = rng.normal(0, 0.5, (3, d))
    mask = np.array([True, True, False])
    outs = {}
    for method in ("ref", "pallas"):
        h, y, ys = core_dispatch.run_decode_fused(
            params.lam_q, params.n_real, w_drive, w_out, states, y_prev,
            mask, 5, use_bias=cfg.use_bias, use_feedback=use_feedback,
            method=method)
        outs[method] = (np.asarray(h), np.asarray(y), np.asarray(ys))
    for a, b in zip(outs["ref"], outs["pallas"]):
        np.testing.assert_allclose(a, b, atol=1e-5)
    # frozen row untouched, live rows moved
    np.testing.assert_array_equal(outs["ref"][0][2], states[2])
    assert not np.allclose(outs["ref"][0][0], states[0])


def test_resolve_decode_method_routing():
    import jax
    expected = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert core_dispatch.resolve_decode_method() == expected
    assert core_dispatch.resolve_decode_method("tpu") == "pallas"
    assert core_dispatch.resolve_decode_method("cpu") == "ref"


# -------------------------------------------------- one DecodeResult type
def test_decode_result_unifies_step_and_fused_paths():
    params, readout, sig = _trained()
    eng = _engine(params, readout, sig, ["a", "b"])
    eng.decode_closed_loop(4)
    eng.decode_step({"a": np.asarray(eng.arena.y_prev[
        eng.sessions["a"].slot]), "b": np.asarray(eng.arena.y_prev[
            eng.sessions["b"].slot])})
    res = eng.collect_decoded()
    assert isinstance(res, DecodeResult)
    assert set(res.keys()) == {"a", "b"} and len(res) == 2 and "a" in res
    assert res["a"].shape == (5, 1)              # 4 fused + 1 step, in order
    kinds = [w["kind"] for w in res.waves]
    assert kinds == ["closed_loop", "step"]
    assert res.waves[0]["fused"] and res.waves[0]["tokens"] == 4
    assert not res.waves[1]["fused"] and res.waves[1]["tokens"] == 1
    assert all("_pending" not in w for w in res.waves)
    # drained: a second collect is empty
    again = eng.collect_decoded()
    assert len(again) == 0 and not again.waves


@pytest.mark.parametrize("weights", ["shared", "per_slot", "mean"])
def test_pallas_slot_tiles_match_ref(weights):
    """20 slots span three 8-slot tiles of the kernel's grid: shared
    weights (one constant block for every tile), per-slot weights
    (slot-tiled blocks) and the mean ensemble (one tile) all match the jnp
    reference."""
    rng = np.random.default_rng(1)
    b, nc, d = 20, 24, 2
    lead = () if weights == "shared" else (b,)
    mag = rng.uniform(0.3, 0.9, lead + (nc,))
    ang = rng.uniform(0, np.pi, lead + (nc,))
    args = (mag * np.cos(ang), mag * np.sin(ang),
            rng.normal(0, 0.5, (b, nc)), rng.normal(0, 0.5, (b, nc)),
            rng.normal(0, 0.5, (b, d)),
            rng.normal(0, 0.3, lead + (d, nc)),
            rng.normal(0, 0.3, lead + (d, nc)),
            rng.normal(0, 0.1, lead + (d, d)), rng.normal(0, 0.1, lead + (d,)),
            rng.normal(0, 0.1, lead + (nc, d)),
            rng.normal(0, 0.1, lead + (nc, d)))
    mask = rng.uniform(size=b) > 0.3
    ensemble = "mean" if weights == "mean" else "off"
    from repro.kernels import ops, ref
    got = ops.decode_fused(*args, mask, k=5, ensemble=ensemble)
    want = ref.decode_fused_ref(*args, mask, k=5, ensemble=ensemble)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)


def test_pallas_refuses_64bit_lanes_on_tpu():
    """A compiled (non-interpret) kernel call with float64 operands is a
    configuration error, not a silent rounding to float32."""
    from repro.kernels.diag_scan import decode_fused_pallas_raw
    z = np.zeros((8, 128))
    with pytest.raises(TypeError, match="32-bit lanes"):
        decode_fused_pallas_raw(z[:1], z[:1], z, z, z, z, z, z[:, :128],
                                z[:1], z, z, z, k=2, interpret=False)


# ----------------------------------------------- one host copy per wave
def _capture(eng, name):
    """Record every token output of the engine's jitted ``name`` (the
    kernel's whole ``ys``, or a single step's ``(max_slots, D)`` rows)."""
    outs = []
    fn = getattr(eng._exec, name)

    def call(*args, **kw):
        arena, ys = fn(*args, **kw)
        outs.append(ys)
        return arena, ys
    setattr(eng._exec, name, call)
    return outs


def _no_device_indexing(monkeypatch):
    """Make indexing or iterating a device array raise: the per-session
    slices and per-token rows the host copy replaces."""
    def boom(*args, **kw):
        raise AssertionError("device array sliced on the host")
    array_type = type(jnp.zeros(()))
    monkeypatch.setattr(array_type, "__getitem__", boom)
    monkeypatch.setattr(array_type, "__iter__", boom)


def _decode_by(path, params, readout, sig, sids):
    """Run one decode of ``path`` on a fresh engine; return the engine and
    sid -> the host rows the kernel emitted for that session."""
    if path in ("batched", "mean"):
        bparams, breadout, _ = _batched_trained()
        eng = ReservoirEngine.from_param_batch(
            bparams, readout=breadout,
            ensemble="mean" if path == "mean" else "off")
        sids = list(range(eng.max_slots))
        for i in sids:
            eng.submit(i, sig[600 + i:700 + i, None])
        eng.flush()
    else:
        eng = _engine(params, readout, sig, sids, decode_wave_tokens=3)
    name = {"driven": "_driven_jit", "step": "_decode_jit"}.get(
        path, "_closed_jit")
    outs = _capture(eng, name)
    if path in ("closed_loop", "batched", "mean"):
        eng.decode_closed_loop(4)
    elif path == "interleave":
        eng._exec._decode_wave(sids)
    elif path == "driven":
        for i, s in enumerate(sids):
            eng.queue_inputs(s, sig[700 + i:705 + i, None])
        eng._exec._driven_wave(sids)
    else:
        eng.decode_step({s: sig[700 + i, None] for i, s in enumerate(sids)})
    (ys,) = outs
    ys = np.asarray(ys)
    if path == "step":
        ys = ys[None]
    return eng, {s: ys[:, eng.sessions[s].slot] for s in sids}


@pytest.mark.parametrize("path", ["closed_loop", "interleave", "driven",
                                  "step", "batched", "mean"])
def test_every_decode_path_drains_host_columns_of_the_wave(path,
                                                           monkeypatch):
    """Whatever path decoded them, the drained tokens are host numpy arrays
    equal bit for bit to the session's column of the kernel's output, and
    the drain indexes no device array."""
    params, readout, sig = _trained()
    eng, want = _decode_by(path, params, readout, sig, ["a", "b", "c"])
    _no_device_indexing(monkeypatch)
    res = eng.collect_decoded()
    assert set(res.tokens) == set(want)
    for s, rows in want.items():
        assert isinstance(res[s], np.ndarray)
        np.testing.assert_array_equal(res[s], rows)
    assert res.pulls == (0 if path == "step" else 1)


def test_one_sid_drain_and_release_match_the_full_drain():
    """Draining session by session, or through ``release``, returns the
    same rows as one drain of every session."""
    params, readout, sig = _trained()
    sids = ["a", "b", "c"]
    engines = [_engine(params, readout, sig, sids) for _ in range(2)]
    for eng in engines:
        eng.decode_closed_loop(3)
        eng.decode_step({s: sig[700, None] for s in sids})
        eng.decode_closed_loop(2, sids=["a", "c"])
    full = engines[0].collect_decoded()
    eng = engines[1]
    parts = {"a": eng.collect_decoded("a")["a"],
             "b": eng.release("b").decoded["b"],
             "c": eng.collect_decoded("c")["c"]}
    for s in sids:
        assert isinstance(parts[s], np.ndarray)
        np.testing.assert_array_equal(parts[s], full[s])
    assert full["a"].shape == (6, 1) and full["b"].shape == (4, 1)
    empty = eng.collect_decoded("a")
    assert empty["a"].shape == (0, 1) and empty.pulls == 0


@pytest.mark.parametrize("form", ["all", "sid"])
@pytest.mark.parametrize("rows", [1, 8])
def test_a_decode_wave_is_copied_to_the_host_once(rows, form, monkeypatch):
    """One closed-loop wave for 1 or 8 sessions makes exactly one host
    copy, whether it is drained at once or one session at a time; the
    dispatch itself waits for nothing and the mapping it returns reads the
    same copy."""
    params, readout, sig = _trained()
    sids = [f"s{i}" for i in range(rows)]
    eng = _engine(params, readout, sig, sids)
    assert eng.max_slots >= rows
    outs = _capture(eng, "_closed_jit")
    out = eng.decode_closed_loop(4)
    _no_device_indexing(monkeypatch)
    if form == "all":
        drains = [eng.collect_decoded()]
    else:
        drains = [eng.collect_decoded(s) for s in sids]
    assert sum(d.pulls for d in drains) == 1
    assert eng.collect_decoded().pulls == 0
    ys = np.asarray(outs[0])
    for s in sids:
        (got,) = [d[s] for d in drains if s in d]
        np.testing.assert_array_equal(got, ys[:, eng.sessions[s].slot])
        np.testing.assert_array_equal(out[s], got)

"""Learn-while-serving: streaming eigenbasis refit, readout pools, growth.

The acceptance bars pinned here:

* **refit parity** — a session streamed through ``decode_step``/``observe``
  accumulates exactly the rows the offline teacher-forced ``fit`` would
  build ("the prompt is the washout"), so ``refit()`` reproduces
  ``esn.fit(u, y, washout=P)`` <= 1e-5 — standard ridge AND the EET
  generalized-metric solve, with and without feedback.  (Parity alpha is
  1e-4: the streamed and offline (G, C) agree to ~1e-13 under x64, but the
  solve amplifies that by cond(G), so a 1e-8 alpha would compare two
  correct solves of an ill-conditioned system, not the accumulation.)
* **tenant isolation** — refitting tenant A leaves tenant B's served
  outputs BIT-EXACT (pool scatter touches only A's slots).
* **typed stats / release** — ``stats()`` is a frozen ``EngineStats``
  (attribute access; dict keys deprecated-but-working for one release),
  ``release(sid, drop=True)`` skips the device gather, ``evict`` stays a
  one-line alias.
* **DPG growth** — drift past threshold grows a fresh ``dpg_params``
  member that trains from the shared teacher stream and joins the
  validation-RMSE-weighted vote.
* **snapshot round-trip** — pools + per-session Gram stats survive
  ``snapshot()``/``restore()``: post-restore refits and decodes agree.
"""
import os
import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import esn as esn_fn
from repro.core import ridge as ridge_mod
from repro.core.esn import ESNConfig, LinearESN
from repro.data.signals import mso_series
from repro.serve import EngineStats, ReservoirEngine
from repro.serve.arena import _ensemble_reduce
from repro.serve.cost import WaveCostModel


def _cfg(use_feedback=True, n=32, seed=7, alpha=1e-4):
    return ESNConfig(n=n, d_in=1, d_out=1, spectral_radius=0.9, leak=0.8,
                     input_scaling=0.5, ridge_alpha=alpha, seed=seed,
                     use_feedback=use_feedback)


def _model(cfg, mode="diag", t=401):
    sig = mso_series(3, t)
    u, y = sig[:-1, None], sig[1:, None]
    std = LinearESN.standard(cfg).fit(u[:200], y[:200], washout=50)
    m = std if mode == "standard" else LinearESN.diagonalized(cfg).ewt_from(std)
    return m, u, y


def _stream(eng, sid, u, y, start, stop):
    for t in range(start, stop):
        eng.decode_step({sid: u[t]})
        eng.observe(sid, y[t])


# ------------------------------------------------- PR-6 shims: tombstone
def test_add_session_prefill_shims_are_gone():
    """The PR-6 deprecation shims are deleted — ``submit()/flush()`` is the
    ONE admission surface (same tombstone pattern as the ``serve.dispatch``
    module check)."""
    assert not hasattr(ReservoirEngine, "add_session")
    assert not hasattr(ReservoirEngine, "prefill")
    # the replacement surface exists, and evict stays as a one-line alias
    for name in ("submit", "flush", "release", "evict", "refit"):
        assert callable(getattr(ReservoirEngine, name))


# ------------------------------------------------------ streaming refit
@pytest.mark.parametrize("use_fb,mode", [(True, "diag"), (False, "diag"),
                                         (True, "standard"),
                                         (False, "standard")])
def test_streaming_refit_matches_offline_fit(use_fb, mode):
    cfg = _cfg(use_feedback=use_fb)
    model, u, y = _model(cfg, mode)
    P = 60
    ref = esn_fn.fit(model.params, u, y, washout=P)
    eng = ReservoirEngine(model, max_slots=2, learn=True, refit_washout=0)
    eng.submit("s", u[:P], y[:P] if use_fb else None)
    eng.flush()
    _stream(eng, "s", u, y, P, u.shape[0])
    w = eng.refit()["s"]
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref.w_out),
                               rtol=0, atol=1e-5)
    # the refit readout is live: the engine serves it on the next step
    np.testing.assert_array_equal(np.asarray(eng.readout_for("s")),
                                  np.asarray(w))


def test_refit_requires_learn_mode():
    cfg = _cfg()
    model, u, y = _model(cfg)
    eng = ReservoirEngine(model, max_slots=1)            # learn=False
    eng.submit("s", u[:60], y[:60])
    eng.flush()
    with pytest.raises(ValueError, match="learn=True"):
        eng.refit("s")
    with pytest.raises(KeyError):
        ReservoirEngine(model, max_slots=1, learn=True).refit("ghost")


def test_flush_refit_true_refits_dirty_sessions():
    cfg = _cfg()
    model, u, y = _model(cfg)
    eng = ReservoirEngine(model, max_slots=2, learn=True)
    eng.submit("s", u[:60], y[:60])
    eng.flush()
    _stream(eng, "s", u, y, 60, 200)
    assert eng.stats().sessions_dirty == 1
    eng.flush(refit=True)
    st = eng.stats()
    assert st.sessions_dirty == 0
    assert st.refit_waves_total == 1 and st.refit_rows_total == 1


def test_decayed_fold_matches_offline_decayed_weights():
    """λ<1 fold across MULTIPLE refit windows carries exactly the weights
    λ^(m-1-i) one decayed offline fit over the whole stream would use —
    folding in chunks is associative."""
    cfg = _cfg(use_feedback=False)
    model, u, y = _model(cfg)
    lam = 0.97
    P, T = 60, 300
    eng = ReservoirEngine(model, max_slots=1, learn=True, refit_washout=0,
                          refit_decay=lam)
    eng.submit("s", u[:P])
    eng.flush()
    # two windows with an intermediate refit: the second fold must decay
    # the first window's stats by λ^m2
    _stream(eng, "s", u, y, P, 200)
    eng.refit("s")
    _stream(eng, "s", u, y, 200, T)
    ls = eng._learn_plane.state["s"]
    eng._learn_plane._fold_acc(ls.acc, model.params)
    # offline decayed reference over ALL rows [P, T)
    states = esn_fn.run(model.params, u[:T])
    x = esn_fn.features(model.params, states)[P:]
    yt = jnp.asarray(y[P:T])
    m = x.shape[0]
    w = lam ** (jnp.arange(m - 1, -1, -1, dtype=x.dtype) / 2.0)
    g_ref, c_ref = ridge_mod.gram_streaming(x * w[:, None], yt * w[:, None])
    np.testing.assert_allclose(np.asarray(ls.acc.gram), np.asarray(g_ref),
                               rtol=0, atol=1e-8)
    np.testing.assert_allclose(np.asarray(ls.acc.cg), np.asarray(c_ref),
                               rtol=0, atol=1e-8)


def test_refit_washout_skips_leading_rows():
    """``refit_washout=k`` drops the first k streamed pairs (sessions
    admitted with a too-short prompt still converge before training)."""
    cfg = _cfg(use_feedback=False)
    model, u, y = _model(cfg)
    P, k = 60, 25
    ref = esn_fn.fit(model.params, u, y, washout=P + k)
    eng = ReservoirEngine(model, max_slots=1, learn=True, refit_washout=k)
    eng.submit("s", u[:P])
    eng.flush()
    _stream(eng, "s", u, y, P, u.shape[0])
    w = eng.refit()["s"]
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref.w_out),
                               rtol=0, atol=1e-5)


def test_interrupted_teacher_stream_pairs_only_contiguous_rows():
    """Rows pair only when exactly ONE decode step separates consecutive
    teacher events: a free-run gap (decode without observe) must not inject
    mismatched (state, truth) rows."""
    cfg = _cfg(use_feedback=False)
    model, u, y = _model(cfg)
    P = 60
    eng = ReservoirEngine(model, max_slots=1, learn=True, refit_washout=0)
    eng.submit("s", u[:P])
    eng.flush()
    _stream(eng, "s", u, y, P, 150)
    pairs_before = len(eng._learn_plane.state["s"].acc.buf_h)
    for t in range(150, 155):          # free-run: no observe
        eng.decode_step({"s": u[t]})
    eng.observe("s", y[155])           # 6 steps since last teacher event
    assert len(eng._learn_plane.state["s"].acc.buf_h) == pairs_before
    _stream(eng, "s", u, y, 156, 200)  # contiguous again: pairs resume
    assert len(eng._learn_plane.state["s"].acc.buf_h) > pairs_before


# ------------------------------------------------- per-tenant readout pools
def _twin(dia, u, y, tenants=("A", "B")):
    eng = ReservoirEngine(dia, max_slots=4, learn=True)
    eng.submit("a", u[:60], y[:60], tenant=tenants[0])
    eng.submit("b", u[:60], y[:60], tenant=tenants[1])
    eng.flush()
    for t in range(60, 200):
        eng.decode_step({"a": u[t], "b": u[t]})
        eng.observe("a", y[t])
        eng.observe("b", y[t])
    return eng


def test_tenant_refit_leaves_other_tenant_bit_exact():
    cfg = _cfg()
    dia, u, y = _model(cfg)
    eng = _twin(dia, u, y)
    eng.decode_step({"b": u[200]})
    eng.observe("b", y[200])
    assert set(eng.refit("a")) == {"a"}          # only tenant A re-solved
    out_b = eng.decode_step({"b": u[201]})["b"]
    # twin engine that never refit A: b's stream must be BIT-identical
    ref = _twin(dia, u, y)
    ref.decode_step({"b": u[200]})
    ref.observe("b", y[200])
    out_ref = ref.decode_step({"b": u[201]})["b"]
    np.testing.assert_array_equal(np.asarray(out_b), np.asarray(out_ref))
    # ...and A actually changed (the refit was not a no-op)
    assert not np.array_equal(np.asarray(eng.readout_for("a")),
                              np.asarray(ref.readout_for("a")))


def test_sessions_sharing_tenant_share_one_readout():
    cfg = _cfg()
    dia, u, y = _model(cfg)
    eng = ReservoirEngine(dia, max_slots=4, learn=True)
    eng.submit("a1", u[:60], y[:60], tenant="A")
    eng.submit("a2", u[:60], y[:60], tenant="A")
    eng.flush()
    for t in range(60, 200):
        eng.decode_step({"a1": u[t], "a2": u[t]})
        eng.observe("a1", y[t])
        eng.observe("a2", y[t])
    eng.refit()
    np.testing.assert_array_equal(np.asarray(eng.readout_for("a1")),
                                  np.asarray(eng.readout_for("a2")))
    # identical streams through one pooled readout -> identical outputs
    out = eng.decode_step({"a1": u[200], "a2": u[200]})
    np.testing.assert_array_equal(np.asarray(out["a1"]),
                                  np.asarray(out["a2"]))


# ------------------------------------------------------- typed EngineStats
def test_stats_is_typed_dataclass_dict_access_removed():
    cfg = _cfg()
    dia, u, y = _model(cfg)
    eng = ReservoirEngine(dia, max_slots=2, learn=True)
    eng.submit("s", u[:60], y[:60])
    eng.flush()
    st = eng.stats()
    assert isinstance(st, EngineStats)
    assert st.sessions_active == 1                       # attribute access
    d = st.to_dict()
    assert d["sessions_active"] == 1 and isinstance(d, dict)
    # The deprecated Mapping compat (one release of DeprecationWarning) is
    # REMOVED: EngineStats is a plain frozen dataclass now.  Pin the
    # removal so the shim cannot quietly return.
    with pytest.raises(TypeError):
        st["sessions_active"]
    assert not hasattr(st, "keys") and not hasattr(st, "__contains__")
    # refit telemetry fields exist from the start
    assert st.refit_waves_total == 0 and st.growth_events == 0


# ------------------------------------------------------- release / evict
def test_release_drop_skips_state_gather():
    cfg = _cfg()
    dia, u, y = _model(cfg)
    eng = ReservoirEngine(dia, max_slots=2, learn=True)
    eng.submit("s", u[:60], y[:60])
    eng.flush()
    eng.decode_step({"s": u[60]})
    r = eng.release("s", drop=True)
    assert r.state is None and r.y_prev is None
    assert np.asarray(r.decoded["s"]).shape[0] == 1      # buffer still drains
    assert "s" not in eng.sessions
    assert "s" not in eng._learn_plane.state                   # learn state freed


def test_evict_is_release_alias():
    cfg = _cfg()
    dia, u, y = _model(cfg)
    eng = ReservoirEngine(dia, max_slots=2)
    eng.submit("s", u[:60], y[:60])
    eng.flush()
    state, y_prev = eng.evict("s")                       # 2-tuple unpack
    assert state.shape == (cfg.n,) and y_prev.shape == (cfg.d_out,)


# ------------------------------------------------------ refit cost surface
def test_cost_model_learns_refit_surface():
    cfg = _cfg()
    dia, u, y = _model(cfg)
    eng = ReservoirEngine(dia, max_slots=2, learn=True, autotune=True)
    eng.submit("s", u[:60], y[:60])
    eng.flush()
    _stream(eng, "s", u, y, 60, 200)
    eng.refit()
    assert eng.cost_model.predict_refit_us(1) >= 1.0
    assert eng.cost_model.predict_refit_us(0) == 0.0     # no rows, no wave
    rec = [r for r in eng.cost_model.records() if r.get("kind") == "refit"]
    assert rec and rec[0]["b"] == 1 and rec[0]["us"] > 0
    # the artifact round-trips the refit surface like every other kind
    seeded = WaveCostModel()
    assert seeded.seed(eng.cost_model.records()) > 0
    assert seeded.predict_refit_us(1) >= 1.0


# ------------------------------------------------- weighted ensemble fusion
def test_weighted_ensemble_reduce_is_normalized_weighted_mean():
    y = jnp.asarray(np.arange(8.0).reshape(4, 2))
    mask = jnp.asarray([True, True, False, True])
    w = jnp.asarray([1.0, 3.0, 100.0, 0.5])             # masked row ignored
    got = np.asarray(_ensemble_reduce(y, mask, w))
    wn = np.asarray([1.0, 3.0, 0.0, 0.5])
    want = (np.asarray(y) * wn[:, None]).sum(0) / wn.sum()
    np.testing.assert_allclose(got, np.broadcast_to(want, y.shape),
                               rtol=0, atol=1e-12)
    # weights=None falls back to the plain masked mean
    got_mean = np.asarray(_ensemble_reduce(y, mask))
    want_mean = np.asarray(y)[np.asarray(mask)].mean(0)
    np.testing.assert_allclose(got_mean[0], want_mean, rtol=0, atol=1e-12)


def test_engine_weighted_ensemble_matches_host_weighted_mean():
    cfg = _cfg(use_feedback=False, n=24)
    sig = mso_series(3, 301)
    u, y = sig[:-1, None], sig[1:, None]
    from repro.core.params import Readout, stack_params
    batch = [esn_fn.dpg_params(_cfg(use_feedback=False, n=24, seed=s), "golden")
             for s in (1, 2, 3)]
    readouts = [esn_fn.fit(p, u[:200], y[:200], washout=50) for p in batch]
    fused = ReservoirEngine.from_param_batch(
        stack_params(batch),
        readout=Readout(jnp.stack([r.w_out for r in readouts])),
        ensemble="weighted")
    weights = [0.2, 0.5, 0.3]
    fused.set_ensemble_weights(weights)
    for i in range(3):
        fused.submit(i, u[:128])
    fused.flush()
    got = fused.decode_step({i: u[128] for i in range(3)})
    singles = []
    for p, r in zip(batch, readouts):
        s = ReservoirEngine(p, max_slots=1, readout=r)
        s.submit("s", u[:128])
        s.flush()
        singles.append(s.decode_step({"s": u[128]})["s"])
    want = sum(w * np.asarray(s) for w, s in zip(weights, singles))
    for i in range(3):
        np.testing.assert_allclose(np.asarray(got[i]), want,
                                   rtol=0, atol=1e-5)


# ------------------------------------------------------- DPG ensemble growth
def test_drift_triggers_dpg_growth_and_member_votes():
    cfg = _cfg()
    dia, u, y = _model(cfg)
    # growth_max_members=1: the clean-stream refit may still sit above the
    # threshold (the readout was refit on corrupted targets), and a SECOND
    # growth event would reset the drift EWMA the final assert reads
    eng = ReservoirEngine(dia, max_slots=2, learn=True,
                          drift_threshold=0.05, growth_washout=8,
                          growth_max_members=1)
    eng.submit("g", u[:60], y[:60])
    eng.flush()
    rng = np.random.default_rng(0)
    for t in range(60, 150):           # corrupt truth: blow the drift EWMA
        eng.decode_step({"g": u[t]})
        eng.observe("g", y[t] + rng.normal(scale=1.0, size=(1,)))
    eng.refit("g")
    assert eng.stats().growth_events >= 1
    ls = eng._learn_plane.state["g"]
    assert ls.members and ls.members[0].w is None        # no vote yet
    _stream(eng, "g", u, y, 150, 220)  # clean stream trains the member
    eng.refit("g")
    assert ls.members[0].w is not None
    out = eng.decode_step({"g": u[220]})
    assert np.isfinite(np.asarray(out["g"])).all()
    assert eng.drift_rmse("g") is not None


def test_growth_capped_at_max_members():
    cfg = _cfg()
    dia, u, y = _model(cfg)
    eng = ReservoirEngine(dia, max_slots=2, learn=True,
                          drift_threshold=1e-6, growth_washout=4,
                          growth_max_members=1)
    eng.submit("g", u[:60], y[:60])
    eng.flush()
    rng = np.random.default_rng(1)
    for k in range(4):                 # four drift excursions, one cap
        for t in range(60 + 30 * k, 90 + 30 * k):
            eng.decode_step({"g": u[t]})
            eng.observe("g", y[t] + rng.normal(scale=1.0, size=(1,)))
        eng.refit("g")
    assert len(eng._learn_plane.state["g"].members) == 1
    assert eng.stats().growth_events == 1


# ------------------------------------------------------ snapshot round-trip
def test_snapshot_restores_pools_and_learn_state():
    cfg = _cfg()
    dia, u, y = _model(cfg)
    eng = ReservoirEngine(dia, max_slots=3, learn=True, refit_decay=0.99)
    eng.submit("a", u[:60], y[:60], tenant="A")
    eng.submit("b", u[:60], y[:60], tenant="B")
    eng.flush()
    for t in range(60, 160):
        eng.decode_step({"a": u[t], "b": u[t]})
        eng.observe("a", y[t])
        eng.observe("b", y[t])
    eng.refit("a")                     # tenant A diverges -> pool active
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "snap")
        eng.snapshot(p)
        eng2 = ReservoirEngine.restore(p)
        np.testing.assert_array_equal(np.asarray(eng.readout_for("a")),
                                      np.asarray(eng2.readout_for("a")))
        # accumulated (G, C) survive: refit of b agrees on both engines
        wb1 = eng.refit("b")["b"]
        wb2 = eng2.refit("b")["b"]
        np.testing.assert_allclose(np.asarray(wb1), np.asarray(wb2),
                                   rtol=0, atol=1e-12)
        o1 = eng.decode_step({"a": u[200], "b": u[200]})
        o2 = eng2.decode_step({"a": u[200], "b": u[200]})
        for s in ("a", "b"):
            np.testing.assert_array_equal(np.asarray(o1[s]),
                                          np.asarray(o2[s]))

"""Hypothesis properties on the learn-while-serving refit path.

These generalize the pinned cases in ``test_learn_serve.py`` across random
prompt lengths, washouts, decay factors, and window splits:

* streaming ``(G, C)`` accumulation + ``refit()`` equals the offline
  ``fit()`` on the concatenated teacher stream <= 1e-5 (EET metric and
  standard ridge, ``refit_washout`` included);
* the λ-decayed fold is associative — folding in chunks at ANY split point
  carries exactly the weights one decayed offline fit would use, and the
  decayed Gram is monotone in window length (more rows never shrink the
  diagonal);
* per-tenant isolation: refitting tenant A is invisible — bit-exact — to
  tenant B's served stream, whatever the streams look like.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep; skip, don't fail collection
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import esn as esn_fn  # noqa: E402
from repro.core import ridge as ridge_mod  # noqa: E402
from repro.core.esn import ESNConfig, LinearESN  # noqa: E402
from repro.data.signals import mso_series  # noqa: E402
from repro.serve import ReservoirEngine  # noqa: E402

# each example builds an engine and compiles a fresh (P, n) prefill trace —
# a handful of examples per property is the budget, not hypothesis' default
SET = settings(max_examples=8, deadline=None)


def _build(seed, use_fb, mode, t=301, n=24):
    cfg = ESNConfig(n=n, d_in=1, d_out=1, spectral_radius=0.9, leak=0.8,
                    input_scaling=0.5, ridge_alpha=1e-4, seed=seed,
                    use_feedback=use_fb)
    sig = mso_series(3, t)
    u, y = sig[:-1, None], sig[1:, None]
    std = LinearESN.standard(cfg).fit(u[:150], y[:150], washout=40)
    m = std if mode == "standard" else LinearESN.diagonalized(cfg).ewt_from(std)
    return m, u, y


def _stream(eng, sid, u, y, start, stop):
    for t in range(start, stop):
        eng.decode_step({sid: u[t]})
        eng.observe(sid, y[t])


@SET
@given(seed=st.integers(0, 50), p=st.integers(40, 72),
       k=st.integers(0, 16), use_fb=st.booleans(),
       mode=st.sampled_from(["diag", "standard"]))
def test_streaming_refit_matches_offline_fit(seed, p, k, use_fb, mode):
    model, u, y = _build(seed, use_fb, mode)
    ref = esn_fn.fit(model.params, u, y, washout=p + k)
    eng = ReservoirEngine(model, max_slots=2, learn=True, refit_washout=k)
    eng.submit("s", u[:p], y[:p] if use_fb else None)
    eng.flush()
    _stream(eng, "s", u, y, p, u.shape[0])
    w = eng.refit()["s"]
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref.w_out),
                               rtol=0, atol=1e-5)


@SET
@given(seed=st.integers(0, 50), lam=st.floats(0.9, 0.999),
       split=st.integers(80, 260))
def test_decayed_fold_is_split_invariant_and_monotone(seed, lam, split):
    model, u, y = _build(seed, False, "diag")
    p, t_end = 60, 280
    split = min(max(split, p + 1), t_end - 1)
    eng = ReservoirEngine(model, max_slots=1, learn=True, refit_washout=0,
                          refit_decay=lam)
    eng.submit("s", u[:p])
    eng.flush()
    _stream(eng, "s", u, y, p, split)
    eng.refit("s")                     # fold window 1 at an arbitrary split
    g1 = np.asarray(eng._learn_plane.state["s"].acc.gram).copy()
    _stream(eng, "s", u, y, split, t_end)
    ls = eng._learn_plane.state["s"]
    eng._learn_plane._fold_acc(ls.acc, model.params)
    # offline decayed reference over ALL rows [p, t_end) in one window
    states = esn_fn.run(model.params, u[:t_end])
    x = esn_fn.features(model.params, states)[p:]
    yt = jnp.asarray(y[p:t_end])
    m = x.shape[0]
    w = lam ** (jnp.arange(m - 1, -1, -1, dtype=x.dtype) / 2.0)
    g_ref, c_ref = ridge_mod.gram_streaming(x * w[:, None], yt * w[:, None])
    np.testing.assert_allclose(np.asarray(ls.acc.gram), np.asarray(g_ref),
                               rtol=0, atol=1e-8)
    np.testing.assert_allclose(np.asarray(ls.acc.cg), np.asarray(c_ref),
                               rtol=0, atol=1e-8)
    # monotone: folding more rows never shrinks the decayed Gram diagonal
    # below the decayed first window (diag entries are sums of λ-weighted
    # squares, and the second fold decays window 1 by exactly λ^m2)
    m2 = m - (split - p)
    floor = (lam ** m2) * np.diag(g1)
    assert (np.diag(np.asarray(ls.acc.gram)) >= floor - 1e-10).all()


@SET
@given(seed=st.integers(0, 50), off_a=st.integers(0, 40),
       off_b=st.integers(0, 40), use_fb=st.booleans())
def test_tenant_refit_leaves_other_tenant_bit_exact(seed, off_a, off_b,
                                                    use_fb):
    model, u, y = _build(seed, use_fb, "diag")
    p = 60

    def run(refit_a):
        eng = ReservoirEngine(model, max_slots=4, learn=True)
        eng.submit("a", u[off_a:off_a + p],
                   y[off_a:off_a + p] if use_fb else None, tenant="A")
        eng.submit("b", u[off_b:off_b + p],
                   y[off_b:off_b + p] if use_fb else None, tenant="B")
        eng.flush()
        for t in range(p, 180):
            eng.decode_step({"a": u[off_a + t], "b": u[off_b + t]})
            eng.observe("a", y[off_a + t])
            eng.observe("b", y[off_b + t])
        if refit_a:
            assert set(eng.refit("a")) == {"a"}
        return np.asarray(eng.decode_step({"b": u[off_b + 180]})["b"])

    np.testing.assert_array_equal(run(True), run(False))

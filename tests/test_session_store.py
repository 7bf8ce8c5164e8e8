"""Tiered session store: park/restore exactness, LRU demotion, snapshots.

The contract under test (serve/store.py + the engine's paging layer):

* parking is **lossless** — a park -> spill -> promote round trip through
  any tier (device arena -> host pool -> cold .npz) returns bit-identical
  ``(state, y_prev)``;
* a paged engine with ``max_slots`` far below the session count serves the
  same tokens as the old caller-managed evict/readmit workflow, with zero
  caller-side state handling (bit-exact at equal arena width; two arenas of
  *different* width differ at fp64 ULP because XLA compiles a different
  fused decode trace per width — that effect predates paging and is pinned
  here so it can't be mistaken for a paging bug);
* demotion victims are chosen least-recently-used first (hypothesis
  property test against a pure-python LRU model);
* ``snapshot()`` / ``restore()`` resume the whole process — arena, parked
  tables, admission queue, un-collected decode buffers — mid-workload;
* ``evict()`` is now a demotion shim and must return the un-collected
  decode tokens instead of dropping them (regression);
* cost artifacts are keyed by ``(backend, n, d_out)`` and shelve foreign or
  legacy un-keyed records instead of fitting them.
"""
import tempfile
import warnings

import numpy as np
import pytest

from repro.core import esn as esn_fn
from repro.core.esn import ESNConfig
from repro.data.signals import mso_series
from repro.serve import (EvictResult, ReservoirEngine, SessionStore,
                         WaveCostModel, cost_key)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # optional dev dep
    HAVE_HYPOTHESIS = False

CFG = ESNConfig(n=24, d_in=1, d_out=1, spectral_radius=0.9, leak=0.8,
                input_scaling=0.5, ridge_alpha=1e-8, seed=7)


def _trained(cfg=CFG):
    sig = mso_series(3, 1201)
    params = esn_fn.diag_params(cfg)
    readout = esn_fn.fit(params, sig[:-1, None], sig[1:, None], washout=50)
    return params, readout, sig


def _prompts(sig, count, t=16, stride=9):
    return {f"s{i}": sig[50 + i * stride:50 + i * stride + t, None]
            for i in range(count)}


# ------------------------------------------------- park/restore exactness
def test_park_round_trips_bit_exact_across_all_tiers():
    """Prefill 12 sessions into a 3-slot arena over a 4-row host pool +
    cold dir: the store must end up using every tier, and each parked
    session's (state, y_prev) must equal the never-parked reference's."""
    params, readout, sig = _trained()
    eng = ReservoirEngine(params, max_slots=3, readout=readout,
                          park_host_rows=4,
                          cold_dir=tempfile.mkdtemp(prefix="tiers_"))
    ref = ReservoirEngine(params, max_slots=12, readout=readout)
    prompts = _prompts(sig, 12)
    for sid, u in prompts.items():
        eng.submit(sid, u)
        ref.submit(sid, u)
    eng.flush()
    ref.flush()
    tiers = {eng.store.tier_of(s) for s in eng.store.sids}
    assert tiers == {"host", "cold"}          # both cold tiers in play
    assert len(eng.parked_sessions) == 9 and len(eng.active_sessions) == 3
    for sid in prompts:
        np.testing.assert_array_equal(np.asarray(eng.state_of(sid)),
                                      np.asarray(ref.state_of(sid)))
    # state_of on a parked session peeks — it must not promote
    parked_before = set(eng.parked_sessions)
    assert set(eng.parked_sessions) == parked_before


def test_feedback_y_prev_survives_park_and_promote():
    """On a feedback model the parked y_prev IS the next step's drive: park
    an observed (teacher-forced) session through the cold tier and the
    promoted decode must match an identically-observed never-parked twin in
    the same-width arena."""
    cfg = ESNConfig(n=24, d_in=1, d_out=1, spectral_radius=0.9, leak=0.8,
                    input_scaling=0.5, use_feedback=True,
                    feedback_scaling=0.3, ridge_alpha=1e-8, seed=11)
    params, readout, sig = _trained(cfg)
    eng = ReservoirEngine(params, max_slots=2, readout=readout,
                          park_host_rows=1,
                          cold_dir=tempfile.mkdtemp(prefix="fb_"))
    ref = ReservoirEngine(params, max_slots=2, readout=readout)
    u, yt = sig[50:66, None], sig[51:67, None]
    y_star = np.asarray([1.25])
    for e in (eng, ref):
        e.submit("fb", u, y_teacher=yt)
        e.flush()
        e.observe("fb", y_star)
    # churn "fb" down to the cold tier: host pool is 1 row, so parking two
    # more sessions pushes the LRU ("fb") out of the pool onto disk
    for i in range(3):
        eng.submit(("churn", i), u, y_teacher=yt)
        eng.flush()
        eng.decode_step({("churn", i): u[0]})
    assert eng.store.tier_of("fb") == "cold"
    got = np.asarray(eng.decode_closed_loop(4, sids=["fb"])["fb"])
    want = np.asarray(ref.decode_closed_loop(4, sids=["fb"])["fb"])
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------ the acceptance scenario
def test_8_slot_paged_engine_serves_64_sessions_like_manual_parking():
    """The tentpole acceptance: a max_slots=8 paged engine serves a
    64-session rotation with ZERO caller-side state handling, bit-exact vs
    the old workflow where the caller evicts, holds, and readmits states
    through an equal-width engine."""
    params, readout, sig = _trained()
    n_sessions, slots, gen = 64, 8, 4
    prompts = _prompts(sig, n_sessions, stride=7)
    sids = list(prompts)
    groups = [sids[i:i + slots] for i in range(0, n_sessions, slots)]

    eng = ReservoirEngine(params, max_slots=slots, readout=readout,
                          park_host_rows=2 * slots,
                          cold_dir=tempfile.mkdtemp(prefix="accept_"))
    for sid in sids:
        eng.submit(sid, prompts[sid])
    eng.flush()
    for sid in sids:                       # seed the closed loop
        eng.observe(sid, prompts[sid][-1] * 0.5)

    ref = ReservoirEngine(params, max_slots=slots, readout=readout)
    parked = {}
    for grp in groups:                     # the old caller-managed workflow
        for sid in grp:
            ref.submit(sid, prompts[sid])
        ref.flush()
        for sid in grp:
            ref.observe(sid, prompts[sid][-1] * 0.5)
            parked[sid] = tuple(np.asarray(a) for a in ref.evict(sid))

    toks_eng, toks_ref = {}, {}
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        for lap in range(2):
            for grp in groups:
                out = eng.decode_closed_loop(gen, sids=grp)
                for sid in grp:
                    toks_eng.setdefault(sid, []).append(np.asarray(out[sid]))
                for sid in grp:
                    h0, y0 = parked.pop(sid)
                    ref.submit(sid, h0=h0, y0=y0)
                ref.flush()
                out = ref.decode_closed_loop(gen, sids=grp)
                for sid in grp:
                    toks_ref.setdefault(sid, []).append(np.asarray(out[sid]))
                    parked[sid] = tuple(np.asarray(a)
                                        for a in ref.evict(sid))
    for sid in sids:
        np.testing.assert_array_equal(np.concatenate(toks_eng[sid]),
                                      np.concatenate(toks_ref[sid]))
    st_ = eng.stats()
    assert st_.promote_waves > 0 and st_.demote_waves > 0


def test_arena_width_ulp_effect_is_not_a_paging_bug():
    """Two UNPAGED engines of different max_slots already differ at fp64 ULP
    on the same session (XLA compiles a different fused decode trace per
    arena width).  Pin that here: the paged engine is held to bit-exactness
    against an equal-width reference (test above), and to this pre-existing
    tolerance against a wider one."""
    params, readout, sig = _trained()
    u = sig[50:66, None]

    def tokens(e):
        e.submit("x", u)
        e.flush()
        e.observe("x", u[-1] * 0.5)
        return np.asarray(e.decode_closed_loop(6, sids=["x"])["x"])

    narrow = tokens(ReservoirEngine(params, max_slots=4, readout=readout))
    wide = tokens(ReservoirEngine(params, max_slots=16, readout=readout))
    paged = tokens(ReservoirEngine(params, max_slots=4, readout=readout,
                                   park_host_rows=4))
    np.testing.assert_array_equal(paged, narrow)   # paging adds NO error
    np.testing.assert_allclose(wide, narrow, rtol=0, atol=1e-12)


# --------------------------------------------------- evict is a shim now
def test_evict_returns_uncollected_decode_tokens():
    """Regression: evict used to drop any decoded-but-uncollected tokens.
    It must return them on the result's ``.decoded`` while still unpacking
    as the legacy ``(state, y_prev)`` pair."""
    params, readout, sig = _trained()
    eng = ReservoirEngine(params, max_slots=2, readout=readout)
    eng.submit("a", sig[50:66, None])
    eng.flush()
    eng.observe("a", sig[66, None])
    eng.decode_closed_loop(5, sids=["a"])          # NOT collected
    res = eng.evict("a")
    assert isinstance(res, EvictResult)
    state, y_prev = res                            # legacy tuple protocol
    assert np.asarray(state).shape == (CFG.n,)
    assert np.asarray(y_prev).shape == (1,)
    assert np.asarray(res.decoded.tokens["a"]).shape == (5, 1)
    # and the buffer is drained — a later collect must not see them again
    assert "a" not in eng.collect_decoded().tokens


def test_evict_returns_tokens_for_parked_session_too():
    params, readout, sig = _trained()
    eng = ReservoirEngine(params, max_slots=2, readout=readout,
                          park_host_rows=4)
    for i in range(4):
        eng.submit(f"s{i}", sig[50 + i:66 + i, None])
    eng.flush()
    eng.observe("s0", sig[66, None])
    eng.decode_closed_loop(3, sids=["s0"])
    # decode s1..s3 to push s0 out of the arena
    for i in (1, 2, 3):
        eng.observe(f"s{i}", sig[66, None])
        eng.decode_closed_loop(1, sids=[f"s{i}"])
    assert "s0" in eng.store
    res = eng.evict("s0")
    assert np.asarray(res.decoded.tokens["s0"]).shape == (3, 1)
    assert "s0" not in eng.store and "s0" not in eng.sessions


# ------------------------------------------------------- LRU demotion law
if HAVE_HYPOTHESIS:
    _OPS = st.lists(
        st.one_of(
            st.tuples(st.just("touch"), st.integers(0, 7)),
            st.tuples(st.just("submit"), st.integers(8, 19)),
            st.tuples(st.just("evict"), st.integers(0, 19))),
        min_size=1, max_size=30)

    @settings(max_examples=15, deadline=None)
    @given(ops=_OPS)
    def test_lru_demotion_matches_pure_python_model(ops):
        """Random submit/touch/evict traffic: the engine's hot/parked split
        must match a pure-python LRU cache model at every step — demotion
        victims are always the least-recently-used eligible sessions."""
        params, readout, sig = _trained()
        slots = 3
        eng = ReservoirEngine(params, max_slots=slots, readout=readout,
                              park_host_rows=8,
                              cold_dir=tempfile.mkdtemp(prefix="lru_"))
        hot, parked = [], set()        # hot: LRU order, oldest first

        def model_demote_for_room():
            while len(hot) >= slots:
                parked.add(hot.pop(0))

        for i in range(slots):         # warm start: fill the arena
            eng.submit(("w", i), sig[50:66, None])
            eng.flush()
            hot.append(("w", i))
        alive = {("w", i) for i in range(slots)}
        for op, k in ops:
            if op == "submit":
                sid = ("n", k)
                if sid in alive:
                    continue
                eng.submit(sid, sig[50:66, None])
                eng.flush()
                model_demote_for_room()
                hot.append(sid)
                alive.add(sid)
            elif op == "touch":
                sid = ("w", k) if k < 3 else ("n", k)
                if sid not in alive:
                    continue
                eng.decode_step({sid: sig[66, None][0]})
                if sid in parked:
                    parked.discard(sid)
                    model_demote_for_room()
                else:
                    hot.remove(sid)
                hot.append(sid)        # most recent
            else:                      # evict
                sid = ("w", k) if k < 3 else ("n", k)
                if sid not in alive:
                    continue
                eng.evict(sid)
                alive.discard(sid)
                parked.discard(sid)
                if sid in hot:
                    hot.remove(sid)
            assert set(eng.active_sessions) == set(hot)
            assert set(eng.parked_sessions) == parked


# ------------------------------------------------------ snapshot / restore
def test_snapshot_restore_resumes_mid_workload():
    """Snapshot an engine that simultaneously has hot sessions, parked
    sessions in BOTH store tiers, a queued prompt, and un-collected decode
    tokens; the restored engine must flush + decode to the same outputs."""
    params, readout, sig = _trained()
    cold = tempfile.mkdtemp(prefix="snapcold_")
    eng = ReservoirEngine(params, max_slots=3, readout=readout,
                          park_host_rows=4, cold_dir=cold, autotune=True)
    prompts = _prompts(sig, 10)
    for sid, u in prompts.items():
        eng.submit(sid, u)
    eng.flush()
    for sid in list(prompts)[:4]:
        eng.observe(sid, prompts[sid][-1] * 0.5)
        eng.decode_closed_loop(2, sids=[sid])      # buffers stay uncollected
    eng.submit("queued", sig[300:316, None])       # NOT flushed
    assert {eng.store.tier_of(s) for s in eng.store.sids} == {"host", "cold"}

    path = tempfile.mkdtemp(prefix="snap_") + "/engine"
    eng.snapshot(path)
    res = ReservoirEngine.restore(path)

    assert set(res.active_sessions) == set(eng.active_sessions)
    assert set(res.parked_sessions) == set(eng.parked_sessions)
    assert len(res.pending) == len(eng.pending) == 1
    # un-collected decode buffers came through
    a = eng.collect_decoded()
    b = res.collect_decoded()
    assert set(a.tokens) == set(b.tokens)
    for sid in a.tokens:
        np.testing.assert_allclose(np.asarray(a.tokens[sid]),
                                   np.asarray(b.tokens[sid]), atol=1e-5)
    # both resume identically: admit the queued prompt, decode everything
    for e in (eng, res):
        e.flush()
    for sid in list(prompts) + ["queued"]:
        e1 = np.asarray(eng.decode_closed_loop(3, sids=[sid])[sid])
        e2 = np.asarray(res.decode_closed_loop(3, sids=[sid])[sid])
        np.testing.assert_allclose(e1, e2, atol=1e-5)
    # restored store writes under a bumped epoch: old cold records are
    # referenced, new spills can't collide with them
    assert res.store.stats()["epoch"] == eng.store.stats()["epoch"] + 1


def test_snapshot_restore_carries_undrained_wave_tokens():
    """Undrained tokens are buffered as handles on whole decode waves (a
    closed-loop wave over two sessions, a single step, a wave over one);
    a snapshot writes them as plain arrays and the restored engine drains
    them as host arrays equal bit for bit to what the kernel emitted, as
    the original engine does."""
    params, readout, sig = _trained()
    eng = ReservoirEngine(params, max_slots=3, readout=readout)
    eng.submit("a", sig[50:66, None])
    eng.submit("b", sig[80:96, None])
    eng.flush()
    outs = []
    for name in ("_closed_jit", "_decode_jit"):
        fn = getattr(eng._exec, name)

        def call(*args, _fn=fn, **kw):
            arena, ys = _fn(*args, **kw)
            outs.append(ys)
            return arena, ys
        setattr(eng._exec, name, call)
    eng.decode_closed_loop(3, sids=["a", "b"])
    eng.decode_step({"a": sig[70, None]})
    eng.decode_closed_loop(2, sids=["b"])
    wave1, step, wave2 = (np.asarray(o) for o in outs)
    sa, sb = eng.sessions["a"].slot, eng.sessions["b"].slot
    want = {"a": np.concatenate([wave1[:, sa], step[sa][None]]),
            "b": np.concatenate([wave1[:, sb], wave2[:, sb]])}

    path = tempfile.mkdtemp(prefix="snap_") + "/engine"
    eng.snapshot(path)
    res = ReservoirEngine.restore(path)
    for drained in (res.collect_decoded(), eng.collect_decoded()):
        assert set(drained.tokens) == {"a", "b"}
        for sid, rows in want.items():
            assert isinstance(drained[sid], np.ndarray)
            np.testing.assert_array_equal(drained[sid], rows)


def test_snapshot_restore_carries_cost_model_key_and_fits():
    params, readout, sig = _trained()
    eng = ReservoirEngine(params, max_slots=2, readout=readout,
                          park_host_rows=2, autotune=True)
    for i in range(4):
        eng.submit(f"s{i}", sig[50 + i:66 + i, None])
        eng.flush()
    path = tempfile.mkdtemp(prefix="snapc_") + "/engine"
    eng.snapshot(path)
    res = ReservoirEngine.restore(path)
    assert res.cost_model.key == eng.cost_model.key
    assert res.cost_model.n_observations == eng.cost_model.n_observations
    assert res._autotune and res.max_slots == 2


# --------------------------------------------------------- guard rails
def test_cold_dir_requires_host_rows():
    params, readout, _ = _trained()
    with pytest.raises(ValueError, match="park_host_rows"):
        ReservoirEngine(params, max_slots=2, readout=readout,
                        cold_dir="/tmp/nope")


def test_paging_rejects_param_batched_engine():
    from repro.core.params import Readout, stack_params
    import jax.numpy as jnp
    # identical seeds keep n_real equal across the stack; the guard under
    # test fires before any numerics run anyway
    batch = [esn_fn.diag_params(CFG) for _ in range(2)]
    params = stack_params(batch)
    sig = mso_series(3, 400)
    readout = Readout(jnp.stack(
        [esn_fn.fit(p, sig[:-1, None], sig[1:, None], washout=50).w_out
         for p in batch]))
    with pytest.raises(ValueError, match="param"):
        ReservoirEngine.from_param_batch(params, readout=readout,
                                         park_host_rows=4)


def test_host_pool_overflow_without_cold_tier_raises():
    params, readout, sig = _trained()
    eng = ReservoirEngine(params, max_slots=1, readout=readout,
                          park_host_rows=1)      # no cold_dir
    for i in range(2):
        eng.submit(f"s{i}", sig[50:66, None])
        eng.flush()
    with pytest.raises(RuntimeError, match="cold"):
        eng.submit("s2", sig[50:66, None])
        eng.flush()


# -------------------------------------------------- cost-model keying
def test_cost_key_shelves_foreign_records():
    m = WaveCostModel(key=cost_key("cpu", 128, 1))
    foreign = [{"b": 2, "t_bucket": 64, "us": 100.0,
                "key": list(cost_key("tpu", 128, 1))}]
    m.seed(foreign)
    assert m.n_observations == 0           # not fitted
    assert foreign[0] in m.records()       # but re-exported verbatim


def test_cost_legacy_unkeyed_records_warn_and_shelve():
    m = WaveCostModel(key=cost_key("cpu", 128, 1))
    legacy = [{"b": 2, "t_bucket": 64, "us": 100.0},
              {"b": 4, "t_bucket": 64, "us": 150.0}]
    with pytest.warns(UserWarning, match="legacy"):
        m.seed(legacy)
    assert m.n_observations == 0
    assert all(r in m.records() for r in legacy)


def test_cost_matching_key_fits_and_roundtrips(tmp_path):
    key = cost_key("cpu", 128, 1)
    m = WaveCostModel(key=key)
    m.observe(2, 64, 100.0)
    m.observe(4, 64, 140.0)
    m.observe_page(2, 50.0)
    m.observe_page(6, 90.0)
    path = str(tmp_path / "cost.json")
    m.to_artifact(path)
    m2 = WaveCostModel.from_artifact(path, key=key)
    assert m2.n_observations == m.n_observations
    assert m2.predict_us(3, 64) == pytest.approx(m.predict_us(3, 64))
    assert m2.predict_page_us(4) == pytest.approx(m.predict_page_us(4))


def test_page_surface_fit_and_priors():
    m = WaveCostModel(page_base_us=200.0, page_per_row_us=2.0)
    assert m.predict_page_us(0) == 0.0
    assert m.predict_page_us(4) == pytest.approx(208.0)   # prior, no obs
    for _ in range(3):
        m.observe_page(2, 120.0)
        m.observe_page(8, 300.0)
    # affine through the (2, 120) and (8, 300) group medians
    assert m.predict_page_us(2) == pytest.approx(120.0)
    assert m.predict_page_us(8) == pytest.approx(300.0)
    assert m.predict_page_us(5) == pytest.approx(210.0)


def test_store_direct_api_spill_and_fetch():
    """SessionStore standalone: park beyond the pool spills LRU to cold,
    fetch pulls from either tier and frees table entries."""
    store = SessionStore(4, 1, np.float64, host_rows=2,
                         cold_dir=tempfile.mkdtemp(prefix="direct_"))

    class S:                                   # engine stats stand-in
        def __init__(self, t):
            self.last_use = t
    states = np.arange(12, dtype=np.float64).reshape(3, 4)
    ys = np.arange(3, dtype=np.float64).reshape(3, 1)
    store.park_many(["a", "b"], states[:2], ys[:2], [S(1), S(2)])
    assert store.tier_of("a") == "host"
    store.park_many(["c"], states[2:], ys[2:], [S(3)])
    assert store.tier_of("a") == "cold"        # LRU spilled
    assert store.tier_of("c") == "host"
    got_s, got_y, got_stats = store.fetch_many(["a", "c"])
    np.testing.assert_array_equal(got_s, states[[0, 2]])
    np.testing.assert_array_equal(got_y, ys[[0, 2]])
    assert [s.last_use for s in got_stats] == [1, 3]
    assert "a" not in store and len(store) == 1

"""Pipelined wave executor: bit-exactness, window bounds, epoch guards.

The contract under test (PR 8, serve/engine.py + serve/store.py +
serve/scheduler.py):

* a pipelined engine (``pipeline_depth >= 1``, async store I/O lane) is
  **bit-exact** vs the strict synchronous baseline (``pipeline_depth=0``,
  ``io_workers=0``) on mixed prefill/decode/park workloads — including
  promoting a parked session while prefill waves are in flight and evicting
  a session whose wave is in flight: the pipeline reorders *host blocking*
  only, never session-visible effects;
* the in-flight window is bounded: never deeper than ``pipeline_depth``,
  and (with a decode SLO set) trimmed until the summed predicted cost of
  the outstanding waves fits the SLO;
* async spill/prefetch completion order can never resurrect a stale
  epoch's data (hypothesis property against a manually-stepped executor);
* ``WaveScheduler.peek_wave`` is exact: ``next_wave`` called with the same
  arguments pops precisely the peeked wave;
* ``--decode-wave-tokens auto``: K resolved per flush from the fitted
  ``c_dec(B, K)`` surface, capped by the decode SLO, and the setting
  survives a snapshot/restore round trip;
* mixed-kind waves: a remainder chunk pads up into the chunk bucket only
  when joining an existing chunk-bucket wave beats a separate dispatch;
* regression (autotune vs async dispatch): wave timings block on the timed
  result *after settling in-flight predecessors*, so a deliberately-async
  dispatch still yields sane ``c(B, T)`` records instead of near-zero (or
  predecessor-inflated) ones.
"""
import tempfile
from concurrent.futures import Future

import numpy as np
import pytest

import jax

from repro.core import esn as esn_fn
from repro.core.esn import ESNConfig
from repro.data.signals import mso_series
from repro.serve import ReservoirEngine, SessionStore, WaveCostModel
from repro.serve.scheduler import PrefillRequest, WaveScheduler

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # optional dev dep
    HAVE_HYPOTHESIS = False

CFG = ESNConfig(n=24, d_in=1, d_out=1, spectral_radius=0.9, leak=0.8,
                input_scaling=0.5, ridge_alpha=1e-8, seed=11)


def _trained(cfg=CFG):
    sig = mso_series(3, 1401)
    params = esn_fn.diag_params(cfg)
    readout = esn_fn.fit(params, sig[:-1, None], sig[1:, None], washout=50)
    return params, readout, sig


def _pair(params, readout, *, depth=2, **kw):
    """(pipelined, synchronous) engines, identical but for the pipeline."""
    pipe = ReservoirEngine(params, readout=readout, pipeline_depth=depth,
                           **kw)
    sync = ReservoirEngine(params, readout=readout, pipeline_depth=0, **kw)
    return pipe, sync


def _assert_same_outputs(out_a, out_b):
    assert set(out_a) == set(out_b)
    for sid in out_a:
        if out_a[sid] is None:
            assert out_b[sid] is None
        else:
            np.testing.assert_array_equal(np.asarray(out_a[sid]),
                                          np.asarray(out_b[sid]))


# ------------------------------------------------------ bit-exact matrix
def test_pipelined_flush_bit_exact_mixed_prefill_decode_park():
    """The full mixed workload on a paged engine: oversubscribed admission
    (park waves), chunked prompts, interleaved closed-loop decode, open-loop
    steps + observe — pipelined and synchronous engines must agree on every
    output and every session state, bit for bit."""
    params, readout, sig = _trained()
    kw = dict(max_slots=4, park_host_rows=6, chunk_max=64,
              decode_slo_us=50_000.0,
              cold_dir=tempfile.mkdtemp(prefix="pipe_a_"))
    pipe, sync = _pair(params, readout, **kw)
    sync.store.cold_dir = tempfile.mkdtemp(prefix="pipe_b_")

    prompts = {f"s{i}": sig[30 + 17 * i:30 + 17 * i + 40 + 8 * (i % 3), None]
               for i in range(10)}
    for eng in (pipe, sync):
        for sid, u in prompts.items():
            eng.submit(sid, u)
        out1 = eng.flush(want_outputs=True)
        # closed-loop decode on explicit hot sids (promotes if parked)
        dec = eng.decode_closed_loop(5, sids=["s1", "s7"])
        # open-loop traffic + teacher forcing
        y = eng.decode_step({"s3": sig[200:201]})
        eng.observe("s3", sig[201:202])
        # a second admission round over the now-crowded store
        for i in range(10, 16):
            eng.submit(f"s{i}", sig[10 * i:10 * i + 33, None])
        out2 = eng.flush(want_outputs=True)
        eng._payload = (out1, dec, y, out2)

    a, b = pipe._payload, sync._payload
    _assert_same_outputs(a[0], b[0])
    _assert_same_outputs(a[1], b[1])
    _assert_same_outputs(a[2], b[2])
    _assert_same_outputs(a[3], b[3])
    for sid in list(prompts) + [f"s{i}" for i in range(10, 16)]:
        np.testing.assert_array_equal(np.asarray(pipe.state_of(sid)),
                                      np.asarray(sync.state_of(sid)))


def test_promote_while_waves_in_flight_bit_exact():
    """Decoding a parked session right after a flush forces a promote while
    the pipelined engine still has prefill waves in flight — the promote
    must settle the window and return the same tokens as the sync engine."""
    params, readout, sig = _trained()
    kw = dict(max_slots=3, park_host_rows=8,
              cold_dir=tempfile.mkdtemp(prefix="pipe_pr_"))
    pipe, sync = _pair(params, readout, **kw)
    sync.store.cold_dir = tempfile.mkdtemp(prefix="pipe_pr2_")
    for eng in (pipe, sync):
        for i in range(8):
            eng.submit(f"p{i}", sig[20 * i:20 * i + 24, None])
        eng.flush()
    # "p0" was demoted (LRU); decoding it promotes mid-pipeline.
    assert "p0" in pipe.parked_sessions and "p0" in sync.parked_sessions
    a = pipe.decode_closed_loop(4, sids=["p0"])
    b = sync.decode_closed_loop(4, sids=["p0"])
    np.testing.assert_array_equal(np.asarray(a["p0"]), np.asarray(b["p0"]))
    # The promote blocked and settled the prefill window; the only entry
    # that may remain in flight is the unblocked decode dispatch itself,
    # which rides the window as a tracked writer.
    assert pipe.stats().pipeline_inflight <= 1


def test_evict_of_in_flight_session_bit_exact():
    """Evicting a session whose prefill wave is still in flight: the
    returned (state, y_prev) ride the data dependency, so they must equal
    the synchronous engine's."""
    params, readout, sig = _trained()
    kw = dict(max_slots=4, park_host_rows=4,
              cold_dir=tempfile.mkdtemp(prefix="pipe_ev_"))
    pipe, sync = _pair(params, readout, **kw)
    sync.store.cold_dir = tempfile.mkdtemp(prefix="pipe_ev2_")
    results = []
    for eng in (pipe, sync):
        for i in range(4):
            eng.submit(f"e{i}", sig[15 * i:15 * i + 20 + i, None])
        eng.flush()
        results.append(eng.evict("e2"))    # wave may still be in flight
    np.testing.assert_array_equal(np.asarray(results[0].state),
                                  np.asarray(results[1].state))
    np.testing.assert_array_equal(np.asarray(results[0].y_prev),
                                  np.asarray(results[1].y_prev))


def test_pipelined_chunked_prompts_bit_exact_unpaged():
    """Chunked long prompts on an unpaged engine (no store => no plan-ahead
    path): the window still bounds dispatch and outputs stay exact."""
    params, readout, sig = _trained()
    kw = dict(max_slots=3, chunk_max=32)
    pipe, sync = _pair(params, readout, **kw)
    outs = []
    for eng in (pipe, sync):
        for i in range(3):
            eng.submit(f"c{i}", sig[40 * i:40 * i + 100, None])
        outs.append(eng.flush(want_outputs=True))
    _assert_same_outputs(outs[0], outs[1])


# ------------------------------------------------------- window invariant
def test_inflight_window_bounded_by_depth():
    params, readout, sig = _trained()
    eng = ReservoirEngine(params, readout=readout, max_slots=4,
                          pipeline_depth=2, park_host_rows=16,
                          cold_dir=tempfile.mkdtemp(prefix="win_"))
    for r in range(3):                      # several flushes, many waves
        for i in range(8):
            eng.submit((r, i), sig[7 * i:7 * i + 16 + 8 * (i % 4), None])
        eng.flush()
    st = eng.stats()
    assert 1 <= st.pipeline_inflight_peak <= 2
    assert st.pipeline_inflight <= 2
    eng.reset()                             # reset drains the window
    assert eng.stats().pipeline_inflight == 0


def test_donated_arena_retires_window_and_matches_sync(monkeypatch):
    """The TPU engine donates the arena to the closed-loop decode, which
    consumes the buffer a queued prefill wave left as its window marker:
    retiring that wave must not block on the donated buffer, and the
    outputs match the synchronous engine."""
    params, readout, sig = _trained()
    with monkeypatch.context() as m:        # the engine decides donation
        m.setattr(jax, "default_backend", lambda: "tpu")     # at build time
        pipe, sync = _pair(params, readout, max_slots=4)
    assert pipe._donate and sync._donate
    outs = []
    for eng in (pipe, sync):
        for i in range(4):
            eng.submit(i, sig[10 * i:10 * i + 100, None])
        eng.flush()
        for _ in range(4):
            eng.decode_closed_loop(2)
        outs.append(eng.collect_decoded().tokens)
    _assert_same_outputs(*outs)


def test_inflight_window_bounded_by_predicted_slo_cost():
    """With a decode SLO set, the summed predicted cost of outstanding
    waves must fit it: a huge predicted wave cost forces depth-1 behavior
    even when pipeline_depth allows more."""
    params, readout, sig = _trained()
    cm = WaveCostModel(base_us=1e9)        # every wave predicts >> slo
    eng = ReservoirEngine(params, readout=readout, max_slots=4,
                          pipeline_depth=4, decode_slo_us=1000.0,
                          cost_model=cm)
    for i in range(8):
        eng.submit(f"w{i}", sig[9 * i:9 * i + 16, None])
    eng.flush()
    assert eng.stats().pipeline_inflight_peak <= 1


def test_sync_mode_never_builds_a_window_and_accounts_blocking():
    params, readout, sig = _trained()
    eng = ReservoirEngine(params, readout=readout, max_slots=4,
                          pipeline_depth=0)
    for i in range(6):
        eng.submit(f"b{i}", sig[11 * i:11 * i + 16, None])
    eng.flush()
    st = eng.stats()
    assert st.pipeline_inflight_peak == 0
    assert st.host_block_us > 0.0       # every wave paid a real block
    # sync engine gets a sync store
    eng2 = ReservoirEngine(params, readout=readout, max_slots=2,
                           pipeline_depth=0, park_host_rows=4)
    assert eng2.store.io_workers == 0
    eng3 = ReservoirEngine(params, readout=readout, max_slots=2,
                           pipeline_depth=2, park_host_rows=4)
    assert eng3.store.io_workers > 0


def test_pipeline_depth_validation():
    params, readout, _ = _trained()
    with pytest.raises(ValueError, match="pipeline_depth"):
        ReservoirEngine(params, readout=readout, pipeline_depth=-1)


# ------------------------------------------------- scheduler: peek == pop
def _mk_req(sid, t, sig):
    return PrefillRequest(sid=sid, u=sig[:t, None])


def _wave_key(wave):
    return [(it.sid, it.start, it.stop, it.first, it.last) for it in wave]


def test_peek_wave_is_exact_preview_of_next_wave():
    _, _, sig = _trained()
    cm = WaveCostModel()
    sched = WaveScheduler(bucket_min=16, chunk_max=32, cost_model=cm)
    lens = [20, 33, 90, 16, 40, 70, 16, 25]
    for i, t in enumerate(lens):
        sched.submit(_mk_req(f"q{i}", t, sig))
    while len(sched):
        peeked = sched.peek_wave(4)
        popped = sched.next_wave(4)
        assert _wave_key(peeked) == _wave_key(popped)
        if not popped:
            break


def test_peek_wave_does_not_mutate_queue_or_deferral():
    _, _, sig = _trained()
    sched = WaveScheduler(bucket_min=16, cost_model=WaveCostModel())
    for i, t in enumerate([16, 16, 64]):
        sched.submit(_mk_req(f"d{i}", t, sig))
    before = [r.sid for r in sched]
    for _ in range(3):
        sched.peek_wave(2)
    assert [r.sid for r in sched] == before
    assert sched._deferred is None


# ------------------------------------------------ store: epoch guard (hyp)
class ManualExecutor:
    """Deterministic executor seam: tasks run either when ``run_all`` is
    called (eager completion) or lazily at ``Future.result()`` (latest
    possible completion) — letting a property drive spill/prefetch
    completions in adversarial orders without threads."""

    def __init__(self):
        self.pending = []

    def submit(self, fn, *args, **kw):
        fut = Future()
        task = (fut, fn, args, kw)
        self.pending.append(task)

        orig_result = fut.result

        def result(timeout=None):
            self._run(task)
            return orig_result(timeout)

        fut.result = result
        return fut

    def _run(self, task):
        fut, fn, args, kw = task
        if task in self.pending:
            self.pending.remove(task)
            try:
                fut.set_result(fn(*args, **kw))
            except BaseException as e:     # pragma: no cover - error path
                fut.set_exception(e)

    def run_all(self):
        while self.pending:
            self._run(self.pending[0])


class _Stats:
    def __init__(self, last_use=0):
        self.last_use = last_use


def _park_distinct(store, sids, n, d_out, base):
    for j, sid in enumerate(sids):
        store.park_many([sid], np.full((1, n), base + j, np.float64),
                        np.full((1, d_out), base + j, np.float64),
                        [_Stats(last_use=j)])


def _epoch_guard_scenario(eager, drain_before_bump):
    """Prefetches submitted under epoch e, completed in ANY order relative
    to an epoch bump (engine restore), must never surface epoch-e bytes
    once the table has moved on: fetch_many re-reads the entry's current
    path instead."""
    cold = tempfile.mkdtemp(prefix="epoch_")
    ex = ManualExecutor()
    store = SessionStore(4, 1, np.float64, host_rows=1, cold_dir=cold,
                         _executor=ex)
    sids = [f"m{i}" for i in range(4)]
    # 1-row pool: each park spills the previous LRU row to cold (async).
    _park_distinct(store, sids, 4, 1, base=0.0)
    cold_sids = [s for s in sids if store.tier_of(s) == "cold"]
    assert len(cold_sids) == 3
    store.prefetch_many(cold_sids)
    # hypothesis picks which futures complete before the epoch bump
    for s, run_now in zip(cold_sids, eager):
        if run_now:
            for task in list(ex.pending):
                ex._run(task)
                break
    if drain_before_bump:
        ex.run_all()
    # --- the epoch moves on (restore): every record is re-written with new
    # payloads at new paths under the new epoch.
    store.epoch += 1
    store._seq = 0
    for j, s in enumerate(cold_sids):
        entry = store.table[s]
        new_path = store._cold_path()
        store._write_record(new_path, np.full((4,), 100.0 + j, np.float64),
                            np.full((1,), 100.0 + j, np.float64))
        entry.path = new_path
    states, ys, _ = store.fetch_many(cold_sids)
    ex.run_all()                           # late completions change nothing
    for j in range(len(cold_sids)):
        np.testing.assert_array_equal(states[j],
                                      np.full((4,), 100.0 + j, np.float64))
    assert not store._prefetch              # stale buffers were dropped


@pytest.mark.parametrize("eager,drain_before_bump", [
    ([False, False, False], False),   # all completions land after the bump
    ([True, True, True], False),      # all land before
    ([True, False, True], False),     # interleaved
    ([False, True, False], True),     # fully drained, then bumped
])
def test_epoch_guard_stale_prefetch_never_resurrects(eager,
                                                     drain_before_bump):
    _epoch_guard_scenario(eager, drain_before_bump)


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(eager=st.lists(st.booleans(), min_size=3, max_size=3),
           drain_before_bump=st.booleans())
    def test_epoch_guard_property(eager, drain_before_bump):
        """Hypothesis sweep over completion orders — same invariant as the
        parametrized scenarios, adversarially sampled."""
        _epoch_guard_scenario(eager, drain_before_bump)


def test_async_spill_round_trip_and_drain():
    """Async spills: table flips to cold immediately, bytes land in the
    background, and fetch/peek block only on the needed future."""
    cold = tempfile.mkdtemp(prefix="spill_")
    ex = ManualExecutor()
    store = SessionStore(4, 1, np.float64, host_rows=1, cold_dir=cold,
                         _executor=ex)
    _park_distinct(store, ["a", "b", "c"], 4, 1, base=5.0)
    assert store.tier_of("a") == "cold" and store.tier_of("b") == "cold"
    assert store.stats()["io_spills_inflight"] == 2
    # peek resolves the pending write lazily, then reads the record
    s, y = store.peek("a")
    np.testing.assert_array_equal(s, np.full((4,), 5.0))
    store.drain_io()
    assert store.stats()["io_spills_inflight"] == 0
    # prefetch + fetch returns the spilled payloads bit-exactly
    store.prefetch_many(["b"])
    states, ys, _ = store.fetch_many(["b", "c"])
    np.testing.assert_array_equal(states[0], np.full((4,), 6.0))
    np.testing.assert_array_equal(states[1], np.full((4,), 7.0))


# ------------------------------------------------------- K-adaptive decode
def test_best_decode_k_monotone_surface_caps_at_kmax_and_slo():
    cm = WaveCostModel()                  # cold affine surface: cpt improves
    assert cm.best_decode_k(4, k_max=16) == 16
    # SLO caps the whole-wave cost: cold c_dec(4, k) = 150 + 4k
    assert cm.best_decode_k(4, slo_us=150 + 4 * 8 + 1, k_max=64) == 8
    # unsatisfiable SLO degrades to K=1, never 0
    assert cm.best_decode_k(4, slo_us=1.0) == 1


def test_best_decode_k_stops_when_marginal_cost_stops_improving():
    cm = WaveCostModel()
    # fit points whose least-squares intercept clamps to 0: the surface
    # degenerates to pure per-token cost, cost/token is FLAT in K, and the
    # scan must stop at K=1 — amortizing a zero dispatch constant buys
    # nothing, so bigger waves would only add reaction latency.
    for us, k in [(100, 1), (190, 2), (500, 4)]:
        for _ in range(3):
            cm.observe_decode(1, us, k=k)
    assert cm.best_decode_k(1, k_max=64) == 1


def test_engine_auto_decode_wave_tokens_resolves_per_flush():
    params, readout, sig = _trained()
    cm = WaveCostModel()
    eng = ReservoirEngine(params, readout=readout, max_slots=4,
                          decode_slo_us=1e6, decode_wave_tokens="auto",
                          cost_model=cm)
    assert eng.decode_wave_tokens == 1     # unresolved until a flush
    for i in range(2):
        eng.submit(f"k{i}", sig[20 * i:20 * i + 24, None])
    eng.flush()
    eng.flush(decode_interleave=True, decode_sids=["k0", "k1"])
    # cold surface: marginal cost/token improves through k_max=64
    assert eng.decode_wave_tokens == 64

    with pytest.raises(ValueError, match="decode_wave_tokens"):
        ReservoirEngine(params, readout=readout, decode_wave_tokens="big")


def test_auto_decode_wave_tokens_survives_snapshot_round_trip():
    params, readout, sig = _trained()
    eng = ReservoirEngine(params, readout=readout, max_slots=3,
                          park_host_rows=4, decode_slo_us=1e6,
                          decode_wave_tokens="auto")
    eng.submit("s", sig[:24, None])
    eng.flush()
    path = tempfile.mkdtemp(prefix="snap_auto_") + "/snap"
    eng.snapshot(path)
    back = ReservoirEngine.restore(path)
    assert back._decode_k_auto
    assert back.pipeline_depth == eng.pipeline_depth


# ---------------------------------------------------- mixed-kind pad-up
def test_remainder_chunk_pads_up_to_join_chunk_bucket_wave():
    _, _, sig = _trained()
    cm = WaveCostModel(base_us=1000.0, per_token_us=0.01)  # dispatch-heavy
    sched = WaveScheduler(bucket_min=16, chunk_max=64, cost_model=cm)
    long_req = PrefillRequest(sid="long", u=sig[:80, None])  # 64 + 16 rem
    long_req.done = 64                    # remainder chunk: 16 tokens
    sched.submit(long_req)
    sched.submit(_mk_req("full", 64, sig))  # rides the chunk bucket
    # joining the 64-bucket wave (marginal ~ beta) beats a separate
    # 16-bucket dispatch (alpha-dominated)
    assert sched.bucket_of(long_req) == 64
    wave = sched.next_wave(4)
    assert {it.sid for it in wave} == {"long", "full"}


def test_remainder_chunk_stays_small_when_no_wave_to_join():
    _, _, sig = _trained()
    cm = WaveCostModel(base_us=1000.0, per_token_us=0.01)
    sched = WaveScheduler(bucket_min=16, chunk_max=64, cost_model=cm)
    req = PrefillRequest(sid="solo", u=sig[:80, None])
    req.done = 64
    sched.submit(req)
    assert sched.bucket_of(req) == 16     # padding with no co-riders: waste


def test_remainder_chunk_stays_small_when_scan_steps_cost_more():
    _, _, sig = _trained()
    cm = WaveCostModel(base_us=1.0, per_token_us=50.0)  # token-heavy
    sched = WaveScheduler(bucket_min=16, chunk_max=64, cost_model=cm)
    req = PrefillRequest(sid="long", u=sig[:80, None])
    req.done = 64
    sched.submit(req)
    sched.submit(_mk_req("full", 64, sig))
    assert sched.bucket_of(req) == 16


def test_padded_wave_outputs_match_unchunked():
    """End to end: a chunked prompt whose remainder padded up into another
    session's chunk-bucket wave still produces the unchunked outputs.
    Padding itself is inert (exact); the comparison is to fp64 ULP because
    the pad-up *changes the wave composition* (a B=2 bucket-64 wave vs the
    reference's two B=1 waves), and XLA compiles a different fused trace
    per (B, T) — the same pre-existing effect test_session_store pins for
    differing arena widths, pinned here so it can't be mistaken for a
    padding bug."""
    params, readout, sig = _trained()
    cm = WaveCostModel(base_us=1000.0, per_token_us=0.01)
    eng = ReservoirEngine(params, readout=readout, max_slots=4,
                          chunk_max=64, cost_model=cm)
    ref = ReservoirEngine(params, readout=readout, max_slots=4)
    for e in (eng, ref):
        e.submit("long", sig[:80, None])
        e.submit("full", sig[100:164, None])
    out = eng.flush(want_outputs=True, method="sequential")
    want = ref.flush(want_outputs=True, method="sequential")
    for sid in ("long", "full"):
        np.testing.assert_allclose(np.asarray(out[sid]),
                                   np.asarray(want[sid]),
                                   rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(eng.state_of("long")),
                               np.asarray(ref.state_of("long")),
                               rtol=1e-12, atol=1e-12)


# ------------------------------------------- autotune timing regression
def test_autotune_timings_block_on_timed_result(monkeypatch):
    """Satellite regression: every autotune-timed wave must block on its
    own result — records from a deliberately-async dispatch regime must be
    real wall times, not near-zero dispatch times."""
    params, readout, sig = _trained()
    eng = ReservoirEngine(params, readout=readout, max_slots=4,
                          autotune=True)
    calls = {"n": 0}
    real_block = jax.block_until_ready

    def counting_block(x):
        calls["n"] += 1
        return real_block(x)

    monkeypatch.setattr(jax, "block_until_ready", counting_block)
    for i in range(4):
        eng.submit(f"t{i}", sig[13 * i:13 * i + 24, None])
    eng.flush()
    monkeypatch.undo()
    recs = [r for r in eng.cost_model.records() if "t_bucket" in r]
    assert recs and calls["n"] >= 1
    # sane wall times: a 24-token CPU wave is microseconds-to-milliseconds,
    # never the ~0 a dispatch-only stamp would record
    assert all(r["us"] > 1.0 for r in recs)
    assert eng.stats().pipeline_inflight == 0


def test_autotune_drains_inflight_predecessors_before_timing():
    """An in-flight predecessor wave must be settled BEFORE the clock
    starts, or its drain time lands inside the timed measurement and
    inflates the c(B, T) record."""
    params, readout, sig = _trained()
    eng = ReservoirEngine(params, readout=readout, max_slots=4,
                          autotune=True)
    # deliberately-async dispatch: a predecessor admitted into the window
    # by hand (autotune flushes never build one on their own)
    lazy = jax.numpy.ones((256, 256)) @ jax.numpy.ones((256, 256))
    eng._inflight.append({"marker": lazy, "pred_us": 1.0,
                          "slots": frozenset(), "arena_after": eng.arena})
    eng.submit("a", sig[:24, None])
    eng.flush()
    assert len(eng._inflight) == 0          # drained, not leaked
    recs = [r for r in eng.cost_model.records() if "t_bucket" in r]
    assert recs and all(r["us"] > 1.0 for r in recs)

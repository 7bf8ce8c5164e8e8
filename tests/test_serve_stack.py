"""Layered serving stack tests: arena / scheduler / engine.

Acceptance bars for the re-layering:

* **Bucketed wave prefill** (``arena.prefill_wave`` via ``submit``/``flush``)
  matches per-session eager ``prefill`` — and the dense O(N^2) hand-rolled
  reference — at <= 1e-5, including feedback mode and rows of mixed true
  lengths inside one padded bucket.
* **Padding is inert**: garbage (not zeros) in the padded tail of a wave row
  cannot change the gathered state or outputs.
* **Scheduler invariants**: oldest-first waves (no starvation across
  buckets), evict-while-queued cancels cleanly.
* **Sharded arena**: engine on a 1x1 local mesh matches the plain engine
  exactly; a 2x1 mesh (subprocess, 2 placeholder devices) matches at
  <= 1e-5.
* **Ensemble mean**: the fused prediction equals the mean of the per-slot
  engines, open and closed loop.
"""
import dataclasses
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import esn as esn_fn
from repro.core.esn import ESNConfig
from repro.core.params import Readout, stack_params
from repro.data.signals import mso_series
from repro.launch.mesh import make_local_mesh
from repro.serve import (PrefillRequest, ReservoirEngine, WaveScheduler,
                         arena as arena_mod, bucket_length)

CFG = ESNConfig(n=48, d_in=1, d_out=1, spectral_radius=0.9, leak=0.8,
                input_scaling=0.5, ridge_alpha=1e-8, seed=7)
CFG_FB = dataclasses.replace(CFG, n=40, use_feedback=True, seed=5)


def _xy(t=600, k=3):
    sig = mso_series(k, t + 1)
    return sig[:-1, None], sig[1:, None]


def _fitted(cfg=CFG, mode="diag", t=600):
    u, y = _xy(t)
    params = (esn_fn.diag_params(cfg) if mode == "diag"
              else esn_fn.standard_params(cfg))
    readout = esn_fn.fit(params, u[:400], y[:400], washout=50)
    return params, readout, u, y


# ------------------------------------------------------------ wave prefill
@pytest.mark.parametrize("mode", ["diag", "standard"])
def test_flush_wave_matches_eager_prefill(mode):
    """One (B, T_bucket) wave == B eager per-session prefills, <= 1e-5,
    with mixed true lengths sharing the bucket."""
    params, readout, u, _ = _fitted(mode=mode)
    lengths = [100, 120, 128, 77]
    prompts = [u[10 * i: 10 * i + t] for i, t in enumerate(lengths)]

    wave_eng = ReservoirEngine(params, max_slots=4, readout=readout)
    for i, p in enumerate(prompts):
        wave_eng.submit(i, p)
    outs = wave_eng.flush(want_outputs=True)
    assert set(outs) == set(range(4))

    eager = ReservoirEngine(params, max_slots=4, readout=readout)
    for i, p in enumerate(prompts):
        eager.submit(i, p)
        want = eager.flush(want_outputs=True)[i]
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(want),
                                   rtol=0, atol=1e-5)
        np.testing.assert_allclose(wave_eng.state_of(i), eager.state_of(i),
                                   rtol=0, atol=1e-5)
    # decode continues identically from the wave-prefilled states
    step = {i: u[300 + i] for i in range(4)}
    got, want = wave_eng.decode_step(step), eager.decode_step(step)
    for i in range(4):
        np.testing.assert_allclose(got[i], want[i], rtol=0, atol=1e-5)


def test_flush_wave_matches_dense_reference():
    """Wave prefill vs the hand-rolled dense O(N^2) oracle."""
    params, readout, u, _ = _fitted(mode="standard")
    w, w_in = np.asarray(params.w), np.asarray(params.w_in)
    w_out = np.asarray(readout.w_out)
    eng = ReservoirEngine(params, max_slots=2, readout=readout)
    eng.submit("a", u[:90])
    eng.submit("b", u[5:105])
    outs = eng.flush(want_outputs=True)
    for sid, prompt in (("a", u[:90]), ("b", u[5:105])):
        r = np.zeros(CFG.n)
        ys = []
        for t in range(prompt.shape[0]):
            r = r @ w + np.asarray(prompt[t]) @ w_in
            ys.append(np.concatenate([[1.0], r]) @ w_out)
        np.testing.assert_allclose(np.asarray(outs[sid]), np.stack(ys),
                                   rtol=0, atol=1e-5)
        np.testing.assert_allclose(eng.state_of(sid), r, rtol=0, atol=1e-5)


def test_flush_wave_feedback_mode_parity():
    """Teacher-forced feedback prefill through a wave: states, outputs and
    the feedback seed all match the eager path (<= 1e-5), mixed lengths."""
    u, y = _xy(500)
    params = esn_fn.standard_params(CFG_FB)
    readout = esn_fn.fit(params, u[:400], y[:400], washout=50)
    lengths = [64, 100]
    wave = ReservoirEngine(params, max_slots=2, readout=readout)
    eager = ReservoirEngine(params, max_slots=2, readout=readout)
    for i, t in enumerate(lengths):
        wave.submit(i, u[:t], y_teacher=y[:t])
    outs = wave.flush(want_outputs=True)
    for i, t in enumerate(lengths):
        eager.submit(i, u[:t], y_teacher=y[:t])
        want = eager.flush(want_outputs=True)[i]
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(want),
                                   rtol=0, atol=1e-5)
        np.testing.assert_allclose(wave.state_of(i), eager.state_of(i),
                                   rtol=0, atol=1e-5)
    # the teacher-seeded feedback column must survive the wave: the next
    # open-loop step uses y_teacher[t-1], so trajectories stay aligned
    step = {i: u[200] for i in range(2)}
    got, want = wave.decode_step(step), eager.decode_step(step)
    for i in range(2):
        np.testing.assert_allclose(got[i], want[i], rtol=0, atol=1e-5)


@pytest.mark.parametrize("use_feedback", [False, True])
def test_wave_padding_steps_are_inert(use_feedback):
    """Garbage (not zeros) in the padded tail of a wave row cannot reach the
    gathered final state, the feedback seed, or the valid outputs — the
    causal gather makes padding inert by construction."""
    cfg = CFG_FB if use_feedback else CFG
    u, y = _xy(300)
    params = esn_fn.standard_params(cfg)
    readout = esn_fn.fit(params, u[:250], y[:250], washout=50,
                         alpha=1e-6)
    t_true, t_pad = 70, 128
    rng = np.random.default_rng(0)

    def run(u_tail, y_tail):
        eng = ReservoirEngine(params, max_slots=1, readout=readout)
        eng.submit("s")
        eng.flush()
        u_pad = np.zeros((1, t_pad, cfg.d_in))
        u_pad[0, :t_true] = u[:t_true]
        u_pad[0, t_true:] = u_tail
        yt = None
        if use_feedback:
            yt = np.zeros((1, t_pad, cfg.d_out))
            yt[0, :t_true] = y[:t_true]
            yt[0, t_true:] = y_tail
        arena, out = arena_mod.prefill_wave(
            params, readout.w_out, eng.arena, jnp.asarray([0]),
            jnp.asarray(u_pad), jnp.asarray([t_true]),
            None if yt is None else jnp.asarray(yt),
            method="sequential", want_outputs=True)
        return (np.asarray(arena.states[0]), np.asarray(arena.y_prev[0]),
                np.asarray(out[0]))

    s0, f0, o0 = run(0.0, 0.0)
    s1, f1, o1 = run(rng.normal(size=(t_pad - t_true, cfg.d_in)) * 100,
                     rng.normal(size=(t_pad - t_true, cfg.d_out)) * 100)
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_array_equal(f0, f1)
    np.testing.assert_array_equal(o0[:t_true], o1[:t_true])
    assert np.all(o1[t_true:] == 0)      # padded outputs are zeroed


# --------------------------------------------------------------- scheduler
def test_bucket_length_powers_of_two():
    assert bucket_length(0) == 0
    assert bucket_length(1) == 16        # bucket_min
    assert bucket_length(16) == 16
    assert bucket_length(17) == 32
    assert bucket_length(128) == 128
    assert bucket_length(129) == 256
    assert bucket_length(5, bucket_min=4) == 8


def test_scheduler_no_starvation_across_buckets():
    """The wave anchors on the global-oldest request: a lone long-prompt
    request behind four short ones is served as soon as they drain, even
    though short requests keep arriving behind it."""
    sch = WaveScheduler(bucket_min=16)
    for i in range(4):
        sch.submit(PrefillRequest(sid=f"short{i}", u=np.zeros((10, 1))))
    sch.submit(PrefillRequest(sid="long", u=np.zeros((100, 1))))
    for i in range(4, 50):               # younger short traffic keeps coming
        sch.submit(PrefillRequest(sid=f"short{i}", u=np.zeros((10, 1))))
    w1 = sch.next_wave(2)
    w2 = sch.next_wave(2)
    assert [r.sid for r in w1] == ["short0", "short1"]
    assert [r.sid for r in w2] == ["short2", "short3"]
    w3 = sch.next_wave(2)                # "long" is now global-oldest
    assert [r.sid for r in w3] == ["long"]


def test_scheduler_wave_is_single_bucket_and_ordered():
    sch = WaveScheduler(bucket_min=16)
    sch.submit(PrefillRequest(sid="a", u=np.zeros((10, 1))))
    sch.submit(PrefillRequest(sid="b", u=np.zeros((100, 1))))
    sch.submit(PrefillRequest(sid="c", u=np.zeros((12, 1))))
    sch.submit(PrefillRequest(sid="d", u=np.zeros((16, 1))))
    wave = sch.next_wave(8)
    # a, c, d share bucket 16; b (bucket 128) is skipped, not reordered
    assert [r.sid for r in wave] == ["a", "c", "d"]
    assert [r.sid for r in sch.next_wave(8)] == ["b"]
    assert sch.next_wave(8) == []


def test_full_slots_leave_queued_fresh_requests_unpriced(monkeypatch):
    """With every slot held, a chunk wave prices only the requests that can
    ride it: the fresh requests queued behind are skipped before their
    bucket is computed, so planning a wave does not grow with the queue."""
    sch = WaveScheduler(bucket_min=16, chunk_max=64)
    for i in range(3):
        sch.submit(PrefillRequest(sid=("held", i), u=np.zeros((200, 1))))
    assert [it.sid for it in sch.next_wave(3)] == [("held", i)
                                                   for i in range(3)]
    for i in range(200):
        sch.submit(PrefillRequest(sid=("fresh", i), u=np.zeros((200, 1))))
    priced = []
    bucket_of = WaveScheduler.bucket_of

    def counted(self, req):
        priced.append(req.sid)
        return bucket_of(self, req)
    monkeypatch.setattr(WaveScheduler, "bucket_of", counted)
    wave = sch.next_wave(0)
    assert [(it.sid, it.start) for it in wave] == [(("held", i), 64)
                                                   for i in range(3)]
    assert priced and all(sid[0] == "held" for sid in priced)


def test_evict_while_queued_cancels_prompt_request():
    params, readout, u, _ = _fitted()
    eng = ReservoirEngine(params, max_slots=1, readout=readout)
    eng.submit("resident")
    eng.flush()
    eng.submit("ghost", u[:50])
    assert len(eng.pending) == 1
    eng.release("ghost")                 # disconnect before admission
    assert len(eng.pending) == 0
    eng.flush()
    assert "ghost" not in eng.sessions   # cancelled, never admitted
    # unknown sids still raise
    with pytest.raises(KeyError, match="neither active nor queued"):
        eng.evict("never-seen")


def test_flush_respects_capacity_and_continues_on_evict():
    params, readout, u, _ = _fitted()
    eng = ReservoirEngine(params, max_slots=2, readout=readout)
    for i in range(5):
        eng.submit(i, u[:64])
    eng.flush()
    assert sorted(eng.sessions) == [0, 1] and len(eng.pending) == 3
    eng.evict(0)                         # prompt requests wait for flush
    assert eng.free_slots == 1 and len(eng.pending) == 3
    eng.flush()
    assert sorted(eng.sessions) == [1, 2] and len(eng.pending) == 2


def test_submit_validates_before_enqueue():
    """Every array is validated at submit() — a bad request must be rejected
    BEFORE it can reach flush(), where the engine has already committed slot
    bookkeeping and a failure would corrupt the session table."""
    u, y = _xy(200)
    params = esn_fn.standard_params(CFG_FB)          # d_out == 1
    eng = ReservoirEngine(params, max_slots=2)
    eng.submit("good", u[:64], y_teacher=y[:64])
    with pytest.raises(ValueError, match="d_out"):
        eng.submit("bad", u[:64], y_teacher=np.zeros((64, 2)))
    with pytest.raises(ValueError):
        eng.submit("bad2", u[:64], y_teacher=y[:64],
                   h0=np.zeros(7))                   # wrong parked-state width
    eng.flush()                                      # good session unharmed
    assert list(eng.sessions) == ["good"]
    assert eng.sessions["good"].tokens_prefilled == 64
    assert len(eng.pending) == 0
    # the admission-only overflow path (submit with no prompt on a full
    # arena) must hold the same invariant: a mis-shaped parked state is
    # rejected at the call site, not when release() later auto-admits it
    eng.submit("filler")
    eng.flush()                                      # queued: arena is full
    assert eng.free_slots == 0
    with pytest.raises(ValueError):
        eng.submit("bad3", h0=np.zeros(7))
    state, _ = eng.evict("good")                     # evict alias still returns state
    assert state.shape == (CFG_FB.n,)


def test_duplicate_submit_rejected():
    params, readout, u, _ = _fitted()
    eng = ReservoirEngine(params, max_slots=1, readout=readout)
    eng.submit("a", u[:32])
    with pytest.raises(KeyError, match="already admitted"):
        eng.submit("a", u[:32])
    eng.flush()
    with pytest.raises(KeyError, match="already admitted"):
        eng.submit("a", u[:32])


# ----------------------------------------------------------- sharded arena
def test_sharded_arena_1x1_matches_plain_engine():
    """mesh=1x1: placement machinery on, numerics bit-identical."""
    params, readout, u, _ = _fitted()
    plain = ReservoirEngine(params, max_slots=2, readout=readout)
    shard = ReservoirEngine(params, max_slots=2, readout=readout,
                            mesh=make_local_mesh(1, 1))
    for eng in (plain, shard):
        eng.submit("a", u[:100])
        eng.submit("b", u[7:107])
        eng.flush()
    for sid in ("a", "b"):
        np.testing.assert_allclose(shard.state_of(sid), plain.state_of(sid),
                                   rtol=0, atol=1e-12)
    for t in range(100, 110):
        got = shard.decode_step({"a": u[t], "b": u[t]})
        want = plain.decode_step({"a": u[t], "b": u[t]})
        for sid in ("a", "b"):
            np.testing.assert_allclose(got[sid], want[sid], rtol=0,
                                       atol=1e-12)
    got = shard.decode_closed_loop(20)
    want = plain.decode_closed_loop(20)
    for sid in ("a", "b"):
        np.testing.assert_allclose(np.asarray(got[sid]),
                                   np.asarray(want[sid]), rtol=0, atol=1e-12)


def test_sharded_arena_2x1_parity_subprocess():
    """2-device local mesh (slots split over `data`) vs single-device: decode
    and wave prefill parity <= 1e-5.  Runs in a subprocess so the main pytest
    process keeps seeing 1 device."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    script = os.path.join(os.path.dirname(__file__), "serve_sharded_check.py")
    out = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "ALL OK" in out.stdout


def test_plan_arena_specs():
    from repro.sharding.rules import plan_arena
    mesh = make_local_mesh(1, 1)
    params = esn_fn.diag_params(CFG)
    plan = plan_arena(mesh, params, 4)
    # 1x1 mesh: every axis degenerates to replicated specs
    assert plan.arena["states"].spec == (None, None) or \
        tuple(plan.arena["states"].spec) == (None, None)
    batch = stack_params([esn_fn.dpg_params(
        dataclasses.replace(CFG, seed=i)) for i in range(2)])
    plan_b = plan_arena(mesh, batch, 2, batched=True,
                        readout=Readout(jnp.zeros((2, CFG.n_features, 1))))
    assert plan_b.readout is not None


# ------------------------------------------------------------ ensemble mean
def _ensemble_fixtures(b=3):
    u, y = _xy(600)
    batch = [esn_fn.dpg_params(dataclasses.replace(CFG, seed=100 + i))
             for i in range(b)]
    readouts = [esn_fn.fit(p, u[:400], y[:400], washout=50) for p in batch]
    stacked = stack_params(batch)
    ro = Readout(jnp.stack([r.w_out for r in readouts]))
    return batch, readouts, stacked, ro, u, y


def test_ensemble_mean_decode_step_is_mean_of_slots():
    batch, readouts, stacked, ro, u, _ = _ensemble_fixtures()
    fused = ReservoirEngine.from_param_batch(stacked, readout=ro,
                                             ensemble="mean")
    singles = []
    for p, r in zip(batch, readouts):
        s = ReservoirEngine(p, max_slots=1, readout=r)
        s.submit("s", u[:128])
        s.flush()
        singles.append(s)
    for i in range(3):
        fused.submit(i, u[:128])
    fused.flush()
    outs = fused.decode_step({i: u[128] for i in range(3)})
    want = np.mean([s.decode_step({"s": u[128]})["s"] for s in singles],
                   axis=0)
    for i in range(3):
        np.testing.assert_allclose(outs[i], want, rtol=0, atol=1e-5)
    # every queried sid sees the SAME fused prediction
    np.testing.assert_array_equal(outs[0], outs[1])


def test_ensemble_mean_closed_loop_feeds_mean_back():
    """Closed loop under ensemble='mean': every reservoir is driven by the
    fused mean — parity vs a host-side loop over individual engines that
    broadcasts the mean as each next input (<= 1e-5, non-feedback model)."""
    batch, readouts, stacked, ro, u, _ = _ensemble_fixtures()
    fused = ReservoirEngine.from_param_batch(stacked, readout=ro,
                                             ensemble="mean")
    singles = []
    for p, r in zip(batch, readouts):
        s = ReservoirEngine(p, max_slots=1, readout=r)
        s.submit("s", u[:128])
        s.flush()
        singles.append(s)
    for i in range(3):
        fused.submit(i, u[:128])
    fused.flush()
    got = fused.decode_closed_loop(15)
    # host reference: step every single engine on the current mean
    y_mean = np.mean([np.asarray(s.y_prev[0]) for s in singles], axis=0)
    ref = []
    for _ in range(15):
        y_mean = np.mean([s.decode_step({"s": y_mean})["s"]
                          for s in singles], axis=0)
        ref.append(y_mean)
    ref = np.stack(ref)
    for i in range(3):
        np.testing.assert_allclose(np.asarray(got[i]), ref, rtol=0,
                                   atol=1e-5)


def test_ensemble_mean_requires_param_batch_and_readout():
    params = esn_fn.diag_params(CFG)
    with pytest.raises(ValueError, match="param-batched"):
        ReservoirEngine(params, max_slots=2, ensemble="mean")
    stacked = stack_params([esn_fn.dpg_params(
        dataclasses.replace(CFG, seed=i)) for i in range(2)])
    with pytest.raises(ValueError, match="param-batched"):
        ReservoirEngine.from_param_batch(stacked, ensemble="mean")
    with pytest.raises(ValueError, match="ensemble"):
        ReservoirEngine(params, max_slots=2, ensemble="median")

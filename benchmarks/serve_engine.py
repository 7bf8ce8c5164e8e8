"""Serving-stack throughput: bucketed waves, sharded arena, lock-step baselines.

Measures the serving phases the three-layer stack separates:

* **prefill.bucketed vs prefill.sequential** — ONE ``(B, T_bucket)`` wave
  through ``arena.prefill_wave`` (``submit`` + ``flush``) vs B eager
  per-session scans (the pre-scheduler engine path).  The acceptance bar:
  >= 2x at B >= 4 on CPU.
* **prefill.autotuned vs prefill.static_wave** — a mixed-length workload
  (three buckets plus one long prompt) served by the cost-model wave planner
  (``autotune=True`` + chunked long prompts) vs the static ``max_wave`` cap
  it replaces.  The acceptance bar: >= 1.2x tok/s on CPU.  The autotuned
  engine's measured wave timings are exported under ``"wave_costs"`` — the
  offline seed ``serve.cost.WaveCostModel.from_artifact`` consumes.
* **mixed.decode_aware vs mixed.decode_blind** — the decode-starvation
  scenario continuous-batching servers gate on: live decoders mid-generation
  while a chunked prefill flood drains.  The decode-blind planner runs every
  runnable prefill wave before the serve loop can decode again (inter-token
  gap ~ one whole flush); decode-aware planning (``decode_slo_us``)
  interleaves closed-loop decode waves whenever the planned prefill cost
  since the decoders' last token hits the SLO.  Reported: decode p50/p95
  inter-token gap and prefill tok/s under both policies.  The acceptance
  bar: p95 bounded (well under the blind drain) at <= 15% prefill tok/s
  cost.
* **prefill / decode vs lock-step** — engine scan / closed loop vs a
  per-token python loop over the jit'd batched step (what
  ``launch/serve.py`` did before the engine existed).
* **decode.fused** — ONE fused K-token kernel dispatch (diag step + readout
  matmul + ensemble reduce + feedback write entirely on-device) for a full
  decode arena, with achieved vs theoretical bytes/token from the compiled
  cost analysis — both gated by the perf trajectory.
* **decode.sharded** — the same closed-loop decode with the arena placed on
  a 1x1 local mesh via ``sharding.rules.plan_arena`` (placement machinery
  on; with one CPU device this prices the overhead, on a pod it prices the
  win).
* **park.restore** — the tiered session store under sessions >> slots churn:
  4x oversubscribed round-robin decode groups, so every decode wave promotes
  a fully-parked group (demoting the previous one through the host pool and
  the cold tier).  Reported: end-to-end tok/s including the page waves, and
  the promote-wave (restore) latency p95 — both trajectory-gated.
* **pipeline.overlap** — the pipelined wave executor (``pipeline_depth=2``
  + async store I/O lane) vs the strict synchronous flush
  (``pipeline_depth=0``, ``io_workers=0``) on the oversubscribed admission
  churn: every round flushes a fresh quarter-arena group (demote page wave
  + host->cold spills) with decode waves mixed in.  Reported: tok/s both
  ways and overlap efficiency = 1 - host_idle/wall — both trajectory-gated.
  The speedup target is >= 1.2x tok/s over the synchronous path.  Caveat:
  overlap needs somewhere to run — the artifact records ``host_cores``, and
  on a single-core host the speedup pins near 1.0x regardless of the
  executor, because host work and the XLA CPU computations timeshare the
  one core (dispatching is async, execution is not parallel).

* **refit.online** — learn-while-serving: the full-arena open-loop teacher
  stream (``decode_step`` + ``observe``) with periodic ``flush(refit=True)``
  readout-refit waves vs the identical load on a frozen-readout engine.
  Mid-stream the teacher signal shifts regime (a sinusoid mix on
  frequencies disjoint from the trained MSO set), so the
  frozen readout stays degraded while the learning engine's decayed
  ``(G, C)`` window recovers.  Reported: tok/s with refits on (trajectory-
  gated), refit overhead vs frozen (acceptance bar: <= 10%), and the
  post-shift RMSE recovery ratio frozen/refit-on (trajectory-gated,
  higher is better).

Plus the full session lifecycle (submit -> flush -> decode -> release with
queued admission) as sessions/sec.
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import numpy as np

import jax

from repro.core import esn as esn_fn
from repro.core.esn import ESNConfig
from repro.launch.mesh import make_local_mesh
from repro.serve import ReservoirEngine, bucket_length

from repro.data.signals import mso_series

from . import _util, roofline


def _build(n):
    cfg = ESNConfig(n=n, spectral_radius=0.95, leak=0.9, input_scaling=0.5,
                    ridge_alpha=1e-8, seed=0)
    params = esn_fn.dpg_params(cfg, "noisy_golden", sigma=0.1)
    sig = mso_series(3, 2001)
    readout = esn_fn.fit_host(params, sig[:-1, None], sig[1:, None],
                               washout=100)
    return params, readout, sig


def main(quick: bool = False):
    n = 256 if quick else 1024
    slots = 4 if quick else 8
    prompt_t = 256 if quick else 1024
    gen_t = 32 if quick else 128
    sessions = 2 * slots
    params, readout, sig = _build(n)
    rng = np.random.default_rng(0)
    prompts = [sig[o:o + prompt_t, None] for o in
               rng.integers(0, len(sig) - prompt_t, size=sessions)]

    res = {"n": n, "slots": slots, "prompt_t": prompt_t, "gen_t": gen_t,
           "sessions": sessions}
    rows = []

    # ---------------- prefill: ONE bucketed wave vs B sequential scans
    wave_eng = ReservoirEngine(params, max_slots=slots, readout=readout)

    def bucketed_prefill():
        wave_eng.reset()
        for s in range(slots):
            wave_eng.submit(s, prompts[s])
        wave_eng.flush()                 # one (B, T_bucket) prefill_wave
        return wave_eng.states

    buck_us = _util.timeit(bucketed_prefill, reps=3, warmup=1)

    seq_eng = ReservoirEngine(params, max_slots=slots, readout=readout)

    def sequential_prefill():
        seq_eng.reset()
        for s in range(slots):
            seq_eng.submit(s, prompts[s])
            seq_eng.flush()              # one-row wave per session: the
        return seq_eng.states            # eager pre-scheduler serving path

    seq_us = _util.timeit(sequential_prefill, reps=3, warmup=1)
    pre_tok = slots * prompt_t
    res["prefill_wave"] = {"bucketed_us": buck_us, "sequential_us": seq_us,
                           "tokens": pre_tok, "b": slots}
    rows.append(_util.csv_row(
        "serve.prefill.bucketed", buck_us,
        f"tok_s={pre_tok / (buck_us * 1e-6):.0f};b={slots}"))
    rows.append(_util.csv_row(
        "serve.prefill.sequential", seq_us,
        f"tok_s={pre_tok / (seq_us * 1e-6):.0f};"
        f"bucketed_speedup=x{seq_us / buck_us:.2f}"))

    # -------- autotuned planner vs the static max_wave cap, mixed lengths
    # Oversubscribed mixed arrivals: a hot bucket (4*slots prompts of the
    # bucket length), short fragments, and one long prompt just past
    # 2*prompt_t — the static path pads it to the 4*prompt_t bucket (nearly
    # half the scan wasted), the autotuned engine drains it as clean
    # prompt_t chunks.  Serve loop = flush / evict-ready until drained
    # (prefill throughput — decode is identical under both policies).  The
    # static baseline caps waves at slots//2 — the conservative hand-tuning
    # the cost model replaces — so the hot bucket fragments into twice as
    # many half-empty waves; the planner runs full waves because its
    # measured c(B, T_bucket) says rows are nearly free.  Both schedules
    # are deterministic (static: ~2x the padded scan-steps); the measured
    # ratio wobbles with machine noise around that structural gap.
    mix = ([prompt_t] * (4 * slots) + [prompt_t // 8] * (slots - 1)
           + [2 * prompt_t + prompt_t // 8])
    long_sig = np.concatenate([sig[:-1]] * (3 * prompt_t // len(sig) + 2))
    mix_prompts = [long_sig[i:i + t, None] for i, t in enumerate(mix)]
    mix_tokens = int(sum(mix))

    def drain(eng):
        eng.reset()
        for s, p in enumerate(mix_prompts):
            eng.submit(s, p)
        while eng.sessions or len(eng.pending):
            eng.flush()
            for s in list(eng.ready_sessions):
                eng.release(s)
        return eng.states

    static_eng = ReservoirEngine(params, max_slots=slots, readout=readout)
    static_eng.scheduler.max_wave = max(1, slots // 2)
    static_us = _util.timeit(drain, static_eng, reps=3, warmup=1)

    # Learn-then-serve, mirroring deployment: an autotune pass measures every
    # wave (per-wave host sync — the price of a measurement), then the timed
    # engine plans with the seeded model and no sync in the serving path.
    # The first drain only warms the traces — its timings include XLA
    # compilation and would skew the affine fits (and the exported seed) by
    # orders of magnitude, so the model is cleared before the real pass.
    learner = ReservoirEngine(params, max_slots=slots, readout=readout,
                              autotune=True, chunk_max=prompt_t)
    drain(learner)                       # compile pass (polluted timings)
    learner.cost_model.clear()
    drain(learner)                       # measurement pass: clean fits
    auto_eng = ReservoirEngine(params, max_slots=slots, readout=readout,
                               cost_model=learner.cost_model,
                               chunk_max=prompt_t)
    auto_us = _util.timeit(drain, auto_eng, reps=3, warmup=1)
    res["prefill_autotuned"] = {"autotuned_us": auto_us,
                                "static_us": static_us,
                                "tokens": mix_tokens,
                                "static_max_wave": static_eng.scheduler.max_wave,
                                "chunk_max": prompt_t,
                                "sessions": len(mix)}
    # records(), not stats()["wave_costs"]: the engine's wave log still
    # remembers the compile pass; the cleared model holds only clean points.
    res["wave_costs"] = learner.cost_model.records()
    rows.append(_util.csv_row(
        "serve.prefill.autotuned", auto_us,
        f"tok_s={mix_tokens / (auto_us * 1e-6):.0f};sessions={len(mix)}"))
    rows.append(_util.csv_row(
        "serve.prefill.static_wave", static_us,
        f"tok_s={mix_tokens / (static_us * 1e-6):.0f};"
        f"autotuned_speedup=x{static_us / auto_us:.2f}"))

    # -------- mixed load: live decoders + chunked prefill flood, decode-
    # aware planner (decode_slo_us) vs the decode-blind PR-4 planner.  Both
    # engines plan with the SAME learned cost model and chunking; the only
    # difference is the SLO, so the deltas are pure scheduling policy.
    dec_n = 2
    mslots = 2 * slots                # bigger arena: the flood is the point
    chunk_len = max(64, prompt_t // 2)
    chunk_bucket = bucket_length(chunk_len)   # the bucket the scheduler uses
    flood_n = int(1.5 * mslots)
    flood_len = 8 * prompt_t          # each flood prompt = 16 chunk waves
    long_mix = np.concatenate([sig[:-1]] * (flood_len // len(sig) + 2))
    flood_prompts = [long_mix[7 * i:7 * i + flood_len, None]
                     for i in range(flood_n)]
    flood_tokens = flood_n * flood_len
    dec_sids = [("dec", i) for i in range(dec_n)]

    def mixed_drain(eng, interleave):
        eng.reset()
        for i, s in enumerate(dec_sids):
            eng.submit(s, prompts[i][:chunk_len])
        eng.flush()
        jax.block_until_ready(
            eng.decode_closed_loop(1, sids=dec_sids)[dec_sids[0]])
        for i in range(flood_n):
            eng.submit(("flood", i), flood_prompts[i])
        while True:
            eng.flush(decode_interleave=interleave)
            # the decode-blind loop can only decode HERE — after the whole
            # flush drained; the aware flush interleaved decode waves inside.
            # Block on the token: a dispatched-but-unmaterialized token is
            # still latency, so the gap percentiles must see real wall time.
            jax.block_until_ready(
                eng.decode_closed_loop(1, sids=dec_sids)[dec_sids[0]])
            for s in list(eng.ready_sessions):
                if s[0] == "flood":
                    eng.release(s)
            if not (len(eng.pending)
                    or any(s[0] == "flood" for s in eng.active_sessions)):
                return eng.states

    # Learn-then-serve on the mixed shape itself: an autotune pass measures
    # these exact (B, chunk_bucket) waves and decode dispatches, so the
    # decode budget is priced in *this* scenario's real wall costs — a model
    # fitted on other shapes underestimates them and the SLO goes soft.
    mixed_learner = ReservoirEngine(params, max_slots=mslots,
                                    readout=readout, autotune=True,
                                    chunk_max=chunk_len)
    mixed_drain(mixed_learner, False)       # compile pass (polluted timings)
    mixed_learner.cost_model.clear()
    mixed_drain(mixed_learner, False)       # measurement pass: clean fits
    # decode surface: the drain loop only ever decodes dec_n rows, so add
    # narrower widths for >= 2 distinct B in the affine fit — autotune
    # times and observes each dispatch itself, and the closed-loop trace is
    # mask-agnostic (already compiled by the drains), so nothing here pays
    # a compile.  Settle the drain's pending async work (evictions,
    # releases) first: the first timed dispatch would otherwise block on it
    # and land an order-of-magnitude outlier in the fit.
    jax.block_until_ready(mixed_learner.states)
    for b in range(1, dec_n + 1):
        for _ in range(3):   # 3 samples/width: the median fit sheds any
            mixed_learner.decode_closed_loop(1, sids=dec_sids[:b])  # stall
    mcost = mixed_learner.cost_model
    # Budget: ~4 full chunk waves of planned prefill between decode waves,
    # plus the decode wave's own predicted cost (the engine reserves it out
    # of the budget) — the blind drain runs ALL runnable chunks back to
    # back (tens of waves per flush), while the decode syncs stay a small
    # tax on prefill tok/s (each interleaved decode wave blocks, trading
    # pipelining for latency; a tighter SLO buys lower p50/p95 at a
    # steeper tok/s price).
    slo_us = (4.0 * mcost.predict_us(mslots - dec_n, chunk_bucket)
              + mcost.predict_decode_us(dec_n, 1))   # drain decodes K=1 waves

    def warm_wave_sizes(eng):
        # The budget trimmer may pop any wave size 1..free; each distinct
        # (B, T_bucket) is its own XLA trace, and a first-call compile
        # landing inside a timed drain would swamp the gap percentiles.
        eng.reset()
        for b in range(1, mslots - dec_n + 1):
            for i in range(b):
                eng.submit(("w", b, i), long_mix[:chunk_len, None])
            eng.flush()
            for i in range(b):
                eng.release(("w", b, i))
        jax.block_until_ready(eng.states)

    def measure_mixed(eng, interleave):
        warm_wave_sizes(eng)
        mixed_drain(eng, interleave)       # compile pass
        # the percentiles must price serving, not XLA compilation
        eng.clear_decode_gaps()
        us = _util.timeit(mixed_drain, eng, interleave, reps=3, warmup=0)
        st = eng.stats()
        nan = float("nan")
        return (us,
                nan if st.decode_gap_p50_us is None
                else st.decode_gap_p50_us,
                nan if st.decode_gap_p95_us is None
                else st.decode_gap_p95_us)

    aware_eng = ReservoirEngine(params, max_slots=mslots, readout=readout,
                                cost_model=mcost,
                                chunk_max=chunk_len, decode_slo_us=slo_us)
    blind_eng = ReservoirEngine(params, max_slots=mslots, readout=readout,
                                cost_model=mcost, chunk_max=chunk_len)
    aware_us, aware_p50, aware_p95 = measure_mixed(aware_eng, True)
    blind_us, blind_p50, blind_p95 = measure_mixed(blind_eng, False)
    # re-export: the artifact seed now carries prefill AND decode surfaces
    # (both scenarios' observations — seed() merges them on load)
    res["wave_costs"] = (learner.cost_model.records() + mcost.records())
    res["mixed_decode_aware"] = {
        "aware_us": aware_us, "blind_us": blind_us, "tokens": flood_tokens,
        "decode_slo_us": slo_us, "decoders": dec_n, "chunk_len": chunk_len,
        "slots": mslots, "flood_sessions": flood_n, "flood_len": flood_len,
        "aware_gap_p50_us": aware_p50, "aware_gap_p95_us": aware_p95,
        "blind_gap_p50_us": blind_p50, "blind_gap_p95_us": blind_p95,
        "interleave_waves":
            aware_eng.stats().decode_interleave_waves}
    rows.append(_util.csv_row(
        "serve.mixed.decode_aware", aware_us,
        f"tok_s={flood_tokens / (aware_us * 1e-6):.0f};"
        f"gap_p95_ms={aware_p95 / 1e3:.1f};"
        f"prefill_cost=x{aware_us / blind_us:.3f}"))
    rows.append(_util.csv_row(
        "serve.mixed.decode_blind", blind_us,
        f"tok_s={flood_tokens / (blind_us * 1e-6):.0f};"
        f"gap_p95_ms={blind_p95 / 1e3:.1f};"
        f"p95_speedup=x{blind_p95 / aware_p95:.1f}"))

    # ---------------- prefill: engine scan vs per-token lock-step loop
    eng = ReservoirEngine(params, max_slots=slots, readout=readout)

    def engine_prefill():
        eng.reset()
        for s in range(slots):
            eng.submit(s, prompts[s])
            eng.flush(want_outputs=True)   # one-row wave with outputs: what
        return eng.states                  # the eager prefill used to return

    eng_pre_us = _util.timeit(engine_prefill, reps=3, warmup=1)

    lock = ReservoirEngine(params, max_slots=slots, readout=readout)
    for s in range(slots):
        lock.submit(s, prompts[s][:1])     # admit via a 1-token wave
    lock.flush()

    def lockstep_prefill():
        out = None
        for t in range(prompt_t):
            out = lock.decode_step(
                {s: prompts[s][t] for s in range(slots)})
        return out[0]

    lock_pre_us = _util.timeit(lockstep_prefill, reps=3, warmup=1)
    pre_tok = slots * prompt_t
    res["prefill"] = {"engine_us": eng_pre_us, "lockstep_us": lock_pre_us,
                      "tokens": pre_tok}
    rows.append(_util.csv_row(
        "serve.prefill.engine", eng_pre_us,
        f"tok_s={pre_tok / (eng_pre_us * 1e-6):.0f}"))
    rows.append(_util.csv_row(
        "serve.prefill.lockstep", lock_pre_us,
        f"tok_s={pre_tok / (lock_pre_us * 1e-6):.0f};"
        f"engine_speedup=x{lock_pre_us / eng_pre_us:.2f}"))

    # ---------------- decode: batched closed loop vs per-token loop
    def engine_decode():
        ys = eng.decode_closed_loop(gen_t)
        return ys[0]

    eng_dec_us = _util.timeit(engine_decode, reps=3, warmup=1)

    def lockstep_decode():
        out = None
        for _ in range(gen_t):
            ys = lock.decode_step(
                {s: np.asarray(lock.y_prev[lock.sessions[s].slot])
                 for s in range(slots)})
            out = ys[0]
        return out

    lock_dec_us = _util.timeit(lockstep_decode, reps=3, warmup=1)
    dec_tok = slots * gen_t
    res["decode"] = {"engine_us": eng_dec_us, "lockstep_us": lock_dec_us,
                     "tokens": dec_tok}
    rows.append(_util.csv_row(
        "serve.decode.engine", eng_dec_us,
        f"tok_s={dec_tok / (eng_dec_us * 1e-6):.0f}"))
    rows.append(_util.csv_row(
        "serve.decode.lockstep", lock_dec_us,
        f"tok_s={dec_tok / (lock_dec_us * 1e-6):.0f};"
        f"engine_speedup=x{lock_dec_us / eng_dec_us:.2f}"))

    # ---------------- decode: the fused K-token kernel at serving batch
    # ONE fused dispatch running K = gen_t tokens for a full decode arena
    # (2x the prefill wave width — decode slots are state-resident, so the
    # arena holds more concurrent decoders than one prefill wave admits).
    # The kernel folds diag step + readout matmul + ensemble reduce +
    # feedback write into that single dispatch; on CPU the per-dispatch
    # host overhead (~hundreds of us) is what K amortizes, on TPU it's the
    # weight HBM traffic.  The roofline terms come from the SAME shapes via
    # compiled cost analysis, so the trajectory gate watches both the
    # throughput and the achieved-vs-theoretical bytes/token ratio.
    dec_k = gen_t
    dec_b = 2 * slots
    fus_eng = ReservoirEngine(params, max_slots=dec_b, readout=readout,
                              decode_wave_tokens=dec_k)
    for s in range(dec_b):
        fus_eng.submit(s, prompts[s])
    fus_eng.flush()

    def fused_decode():
        out = fus_eng.decode_closed_loop(dec_k)
        fus_eng.collect_decoded()          # drain the token buffers
        return out[0]

    fus_dec_us = _util.timeit(fused_decode, reps=3, warmup=1)
    fus_tok = dec_b * dec_k
    res["decode_fused"] = {"us": fus_dec_us, "tokens": fus_tok,
                           "k": dec_k, "b": dec_b,
                           "b4_engine_us": eng_dec_us}
    res["decode_fused"].update(
        roofline.fused_decode_cost(n=n, b=dec_b, k=dec_k))
    rows.append(_util.csv_row(
        "serve.decode.fused", fus_dec_us,
        f"tok_s={fus_tok / (fus_dec_us * 1e-6):.0f};k={dec_k};b={dec_b};"
        f"bytes_ratio={res['decode_fused']['bytes_ratio']:.3f}"))

    # ---------------- decode with the arena placed on a local mesh
    sh_eng = ReservoirEngine(params, max_slots=slots, readout=readout,
                             mesh=make_local_mesh(1, 1))
    for s in range(slots):
        sh_eng.submit(s, prompts[s])
    sh_eng.flush()

    def sharded_decode():
        return sh_eng.decode_closed_loop(gen_t)[0]

    sh_dec_us = _util.timeit(sharded_decode, reps=3, warmup=1)
    res["decode_sharded"] = {"us": sh_dec_us, "mesh": "1x1",
                             "single_device_us": eng_dec_us}
    rows.append(_util.csv_row(
        "serve.decode.sharded", sh_dec_us,
        f"tok_s={dec_tok / (sh_dec_us * 1e-6):.0f};mesh=1x1;"
        f"vs_single=x{eng_dec_us / sh_dec_us:.2f}"))

    # ------------- tiered store: promote/demote churn, sessions >> slots
    # 4x oversubscription with a host pool of 2*slots rows: at any moment
    # one group is hot, two groups fit in the host pool, and the remaining
    # group lives in the cold tier — so the round-robin decode laps exercise
    # BOTH page paths (device<->host and host<->disk) every rotation.
    park_sessions = 4 * slots
    park_gen = max(8, gen_t // 4)
    park_eng = ReservoirEngine(params, max_slots=slots, readout=readout,
                               park_host_rows=2 * slots,
                               cold_dir=tempfile.mkdtemp(prefix="serve_cold_"))
    for s in range(park_sessions):
        park_eng.submit(("park", s), prompts[s % len(prompts)])
    park_eng.flush()
    park_groups = [[("park", g * slots + i) for i in range(slots)]
                   for g in range(park_sessions // slots)]

    def park_churn():
        out = None
        for grp in park_groups:        # each group decode = one full page
            out = park_eng.decode_closed_loop(park_gen, sids=grp)[grp[0]]
        park_eng.collect_decoded()     # don't let token buffers grow
        return out

    park_churn()                       # compile pass (traces + page scatter)
    park_eng._agg.promote_us.clear()   # p95 must price serving, not compiles
    park_us = _util.timeit(park_churn, reps=3, warmup=0)
    park_tok = park_sessions * park_gen
    pst = park_eng.stats()
    nan = float("nan")
    park_p95 = pst.promote_us_p95
    res["park_restore"] = {
        "us": park_us, "tokens": park_tok, "sessions": park_sessions,
        "slots": slots, "host_rows": 2 * slots, "gen": park_gen,
        "promote_waves": pst.promote_waves,
        "demote_waves": pst.demote_waves,
        "page_rows": pst.page_rows_total,
        "restore_p95_us": nan if park_p95 is None else park_p95}
    rows.append(_util.csv_row(
        "serve.park.restore", park_us,
        f"tok_s={park_tok / (park_us * 1e-6):.0f};"
        f"sessions={park_sessions};slots={slots};"
        f"restore_p95_ms={res['park_restore']['restore_p95_us'] / 1e3:.1f}"))

    # -------- pipelined vs synchronous flush: oversubscribed mixed churn
    # The PR 7 oversubscribed shape, driven as admission churn: every round
    # admits a fresh half-arena group, so each flush pays a demote page
    # wave (device->host gather + host-pool park) and — once the pool
    # laps — host->cold spill writes, with decode waves mixed in.  The
    # pipelined engine (pipeline_depth=2 + async store I/O) overlaps that
    # host work with the in-flight prefill scans; the synchronous engine
    # (pipeline_depth=0, io_workers=0) serializes it.  Reported: tok/s
    # both ways and overlap efficiency = 1 - host_idle/wall, where
    # host_idle is the engine's measured block_until_ready time.
    # Arena geometry: one wave admits a quarter of the slots.
    ov_slots = 4 * slots
    ov_grp = slots
    ov_rounds = 12 if quick else 16
    ov_kw = dict(max_slots=ov_slots, readout=readout,
                 park_host_rows=2 * ov_slots)
    ov_pipe = ReservoirEngine(params, pipeline_depth=2,
                              cold_dir=tempfile.mkdtemp(prefix="ov_p_"),
                              **ov_kw)
    ov_sync = ReservoirEngine(params, pipeline_depth=0,
                              cold_dir=tempfile.mkdtemp(prefix="ov_s_"),
                              **ov_kw)

    def ov_workload(eng):
        eng.reset()
        for r in range(ov_rounds):
            for i in range(ov_grp):
                eng.submit((r, i),
                           prompts[(r * ov_grp + i) % len(prompts)])
            eng.flush()
            if r % 4 == 3:         # mixed traffic: decode the fresh group
                eng.decode_closed_loop(
                    4, sids=[(r, i) for i in range(ov_grp)])
                eng.collect_decoded()
        jax.block_until_ready(eng.states)   # settle the in-flight window
        eng.store.drain_io()                # ...and the async spill lane

    def ov_time(eng):
        blocked0 = eng.stats().host_block_us
        t0 = time.perf_counter()
        ov_workload(eng)
        wall = (time.perf_counter() - t0) * 1e6
        return wall, eng.stats().host_block_us - blocked0

    # Interleaved min-of-reps: pipelined and sync reps alternate so machine
    # -state drift between the two measurement blocks cancels instead of
    # showing up as a phantom (anti-)speedup.
    ov_workload(ov_pipe)                    # compile passes
    ov_workload(ov_sync)
    pipe_us, pipe_block, sync_us = float("inf"), 0.0, float("inf")
    for _ in range(4):
        wall, block = ov_time(ov_pipe)
        if wall < pipe_us:
            pipe_us, pipe_block = wall, block
        sync_us = min(sync_us, ov_time(ov_sync)[0])
    ov_tok = (ov_rounds * ov_grp * prompt_t
              + (ov_rounds // 4) * ov_grp * 4)
    ov_eff = (1.0 - pipe_block / pipe_us) if pipe_us > 0 else nan
    res["pipeline_overlap"] = {
        "pipelined_us": pipe_us, "sync_us": sync_us, "tokens": ov_tok,
        "speedup": sync_us / pipe_us if pipe_us > 0 else nan,
        "host_idle_us": pipe_block,
        "overlap_efficiency": ov_eff,
        "rounds": ov_rounds, "group": ov_grp, "slots": ov_slots,
        "host_cores": os.cpu_count(),
        "inflight_peak": ov_pipe.stats().pipeline_inflight_peak}
    rows.append(_util.csv_row(
        "serve.pipeline.overlap", pipe_us,
        f"tok_s={ov_tok / (pipe_us * 1e-6):.0f};"
        f"vs_sync=x{res['pipeline_overlap']['speedup']:.2f};"
        f"overlap_eff={ov_eff:.2f}"))

    # -------- learn-while-serving: streaming refit overhead + drift recovery
    # Mixed open-loop serve load (decode_step + observe teacher stream over
    # a full arena) with periodic flush(refit=True) waves vs the same load
    # on a frozen-readout engine — the refit overhead bar is <= 10% tok/s.
    # Mid-stream the teacher signal switches MSO component count (a regime
    # shift the trained readout has never seen): the frozen engine's RMSE
    # stays degraded, the learning engine's decayed (G, C) window fades the
    # old regime and the next refit waves recover — reported as the
    # post-shift RMSE ratio (frozen / refit-on, higher is better).
    re_tokens = 512 if quick else 1024
    re_every = 64
    re_prompt = 128
    shift = re_tokens // 2
    # Section-local model: the RMSE story needs *finite values*, which the
    # shared ``_build`` params cannot deliver in float32 — ``noisy_golden``
    # at sigma=0.1 pushes |lambda|max past 1 for n >= 256 (divergent scan),
    # and alpha=1e-8 is far below float32 Cholesky conditioning.  Timing
    # sections never noticed (they only measure), this one reports values.
    re_cfg = ESNConfig(n=n, spectral_radius=0.95, leak=0.9,
                       input_scaling=0.5, ridge_alpha=1.0, seed=0)
    re_params = esn_fn.dpg_params(re_cfg, "noisy_golden", sigma=0.01)
    re_readout = esn_fn.fit_host(re_params, sig[:-1, None], sig[1:, None],
                                 washout=100)
    # Post-shift regime: frequencies DISJOINT from the trained MSO set —
    # mso_series(k-1) would be a spectral subset the linear readout predicts
    # perfectly, i.e. no drift at all.
    ts_b = np.arange(len(sig))
    sig_b = np.sin(0.57 * ts_b) + np.sin(1.13 * ts_b) + np.sin(0.31 * ts_b)
    re_stream = np.concatenate([sig[re_prompt:re_prompt + shift],
                                sig_b[:re_tokens - shift + 1]])
    re_sids = list(range(slots))

    def refit_load(eng, refit):
        eng.reset()
        for s in re_sids:
            eng.submit(s, sig[:re_prompt, None])
        eng.flush()
        errs = []
        for t in range(re_tokens):
            out = eng.decode_step({s: re_stream[t, None] for s in re_sids})
            errs.append(float(out[re_sids[0]][0]) - float(re_stream[t + 1]))
            for s in re_sids:
                eng.observe(s, re_stream[t + 1, None])
            if refit and (t + 1) % re_every == 0:
                eng.flush(refit=True)
        eng.collect_decoded()
        jax.block_until_ready(eng.states)
        return errs

    re_learn = ReservoirEngine(re_params, max_slots=slots,
                               readout=re_readout,
                               learn=True, refit_decay=0.98)
    re_frozen = ReservoirEngine(re_params, max_slots=slots,
                                readout=re_readout)
    refit_load(re_learn, True)               # compile passes
    refit_load(re_frozen, False)
    learn_us, frozen_us = float("inf"), float("inf")
    learn_errs = frozen_errs = None
    warm_wave_us = float("inf")
    ratios = []
    # The refit share is small and pass wall time is preemption-noisy on a
    # shared box, so the overhead estimator must reject spikes: pair each
    # learn pass with the frozen pass run RIGHT AFTER it (adjacent passes
    # share the noise regime) and take the MEDIAN of the per-pair ratios —
    # min-of-reps still reports the noise-floor times for tok/s.
    for _ in range(3):
        rs0 = re_learn.stats()
        t0 = time.perf_counter()
        errs = refit_load(re_learn, True)
        us = (time.perf_counter() - t0) * 1e6
        rs1 = re_learn.stats()
        if us < learn_us:
            learn_us, learn_errs = us, errs
        # warm per-wave refit cost straight off the engine's own counters
        # (the all-time mean would be polluted by the compile pass)
        dw = rs1.refit_waves_total - rs0.refit_waves_total
        if dw:
            warm_wave_us = min(warm_wave_us,
                               (rs1.refit_us_sum - rs0.refit_us_sum) / dw)
        t0 = time.perf_counter()
        f_errs = refit_load(re_frozen, False)
        f_us = (time.perf_counter() - t0) * 1e6
        if f_us < frozen_us:
            frozen_us, frozen_errs = f_us, f_errs
        ratios.append(us / f_us)

    def _rmse(e):
        a = np.asarray(e, float)
        return float(np.sqrt(np.mean(a * a))) if a.size else nan

    nan = float("nan")
    re_tok = re_tokens * slots
    tail = re_tokens - re_tokens // 4        # settled post-shift window
    learn_post = _rmse(learn_errs[tail:])
    frozen_post = _rmse(frozen_errs[tail:])
    recovery = (frozen_post / learn_post
                if learn_post and np.isfinite(learn_post)
                and np.isfinite(frozen_post) else nan)
    overhead = float(np.median(ratios)) - 1.0
    lst = re_learn.stats()
    res["refit_online"] = {
        "refit_us": learn_us, "frozen_us": frozen_us, "tokens": re_tok,
        "sessions": slots, "refit_every": re_every,
        "overhead": overhead,
        "refit_waves": lst.refit_waves_total,
        "refit_rows": lst.refit_rows_total,
        "refit_wave_us_warm": (None if warm_wave_us == float("inf")
                               else warm_wave_us),
        "rmse_post_shift_refit": learn_post,
        "rmse_post_shift_frozen": frozen_post,
        "recovery": recovery}
    rows.append(_util.csv_row(
        "serve.refit.online", learn_us,
        f"tok_s={re_tok / (learn_us * 1e-6):.0f};"
        f"overhead={overhead * 100:.1f}%;"
        f"recovery=x{recovery:.1f}"))

    # ---------------- full lifecycle with queued admission
    life_eng = ReservoirEngine(params, max_slots=slots, readout=readout)

    def lifecycle():
        e = life_eng
        e.reset()
        for s in range(sessions):
            e.submit(s, prompts[s % len(prompts)])
        while e.active_sessions or len(e.pending):
            e.flush()                    # bucketed wave prefill
            wave = list(e.active_sessions)
            e.decode_closed_loop(gen_t, sids=wave)
            for s in wave:
                e.release(s)
        return e.states

    life_us = _util.timeit(lifecycle, reps=2, warmup=1)
    res["lifecycle"] = {"us": life_us, "sessions": sessions}
    rows.append(_util.csv_row(
        "serve.lifecycle", life_us,
        f"sessions_s={sessions / (life_us * 1e-6):.1f}"))

    _util.save_artifact("serve_engine.json", res)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", default=True,
                    help="reduced sizes (default when run directly)")
    ap.add_argument("--full", dest="quick", action="store_false")
    for r in main(quick=ap.parse_args().quick):
        print(r)

"""Serving drivers: the ReservoirEngine session loop + the LM smoke loop.

Reservoir serving (the paper's O(N)-step streaming path) — sessions arrive,
queue in the wave scheduler, are admitted in same-bucket waves (each wave ONE
batched prefill), free-run closed-loop decode in lock-step, and are evicted
(their state returned for parking):

    PYTHONPATH=src python -m repro.launch.serve --reservoir \
        --sessions 16 --slots 4 --prompt-len 256 --gen 64

``--mesh DxM`` places the slot arena on a (data, model) device mesh (slots
data-parallel, N TP-sharded — ``sharding.rules.plan_arena``); ``--bucket``
sets the smallest prefill bucket; ``--ensemble mean`` fuses the per-slot
reservoir predictions of a param-batched engine into one output.
``--autotune`` times every wave and lets the cost-model two-wave lookahead
plan wave sizes/buckets by predicted tok/s (seed it offline from a benchmark
artifact via ``--cost-seed artifacts/serve_engine.json``); ``--chunk-max``
splits long prompts into sequential chunk waves so one huge prompt cannot
monopolize the arena.

``--decode-slo US`` turns on decode-aware planning: flushes interleave
closed-loop decode waves whenever the predicted prefill cost since the ready
decoders' last token would exceed the budget (combine with ``--chunk-max``
so decode waves can preempt *within* a long flush, not just between
flushes), and the demo loop mixes open-loop traffic in (teacher-forced
``decode_step`` + ``observe``) alongside the closed-loop generation.
``--decode-wave-tokens K`` sizes those waves: each is ONE fused K-token
kernel dispatch (diag step + readout + feedback write entirely on-device).
``--cost-save PATH`` persists the engine's refined cost model on shutdown
(``WaveCostModel.to_artifact``); point ``--cost-seed`` at the same path to
reload it on the next start — the learned model now survives the process.
Cost artifacts are keyed by ``(backend, n, d_out)``: a seed recorded on a
different backend or model shape is shelved with a warning instead of
poisoning this run's fits.

``--park-host-rows R`` turns on the tiered session store: the slot arena
becomes a cache of hot sessions over a pinned host-memory pool of R rows
(plus an optional ``--cold-dir`` disk tier behind it), so ``--sessions`` can
exceed ``--slots`` without the caller ever touching state — a full arena
parks its least-recently-used idle sessions in batched page waves and decode
on a parked session transparently promotes it back.  ``--snapshot PATH``
serializes the whole engine (arena + parked table + queue + cost model) on
shutdown; ``ReservoirEngine.restore(PATH)`` resumes it bit-exactly.

``--tracker jsonl:PATH`` streams every serving event (prefill / decode /
page / refit / frontend) to a replayable JSON-lines trace through the
pluggable ``serve.telemetry.Tracker`` seam; the ``stats()`` counters are
derived from the same event stream, so trace and counters can never
disagree.  ``--profile-dir DIR`` runs the serving run under
``jax.profiler.trace(DIR)``: one ``.xplane.pb`` holds the device's ops
and the engine's ``serve.*`` host spans (planning, waves, dispatches,
blocks on the device) on one clock.

LM smoke loop (token-synchronous prefill + lock-step decode over the
transformer/hybrid archs — KV/state caches):

    PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b \
        --smoke --batch 4 --gen 32

On a TPU fleet the same code runs under the production mesh with the decode
sharding profile (weights TP-sharded, KV sequence-sharded — see
sharding/rules.py).
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------- reservoir
#: Noise on the DPG golden spectrum.  The paper's 0.2, and 0.1, push
#: max|lambda| past 1 at N >= 256: a reservoir that diverges over a long
#: stream.  0.01 keeps it within 0.956 at N=1024 (seeds 0-5).
DPG_SIGMA = 0.01


def mso_deployment(n: int, seed: int, train_t: int = 2000):
    """The repo's reservoir deployment: one-step-ahead forecasting of the
    3-sine MSO signal.  Returns its ``ESNConfig`` and a training signal of
    ``train_t + 1`` samples."""
    from repro.core.esn import ESNConfig
    from repro.data.signals import mso_series
    cfg = ESNConfig(n=n, spectral_radius=0.95, leak=0.9, input_scaling=0.5,
                    ridge_alpha=1e-8, seed=seed)
    return cfg, mso_series(3, train_t + 1)


def build_reservoir_engine(cfg, sig, *, slots: int, **engine_kw):
    """DPG ``noisy_golden`` params for ``cfg`` (noise :data:`DPG_SIGMA`), a
    readout fit to ``sig`` on the host in float64 (``core.esn.fit_host``),
    and a ``ReservoirEngine`` of ``slots`` slots over them.  The engine
    serves in the params' dtype: float32 unless x64 is on."""
    from repro.core import esn as esn_fn
    from repro.serve import ReservoirEngine
    params = esn_fn.dpg_params(cfg, "noisy_golden", sigma=DPG_SIGMA)
    readout = esn_fn.fit_host(params, sig[:-1, None], sig[1:, None],
                              washout=100)
    return ReservoirEngine(params, max_slots=slots, readout=readout,
                           **engine_kw)


def serve_reservoir(args) -> None:
    """Streaming session serving through ``serve.engine.ReservoirEngine``.

    The model is the pytree-native param API: an immutable ``DiagParams``
    struct from ``dpg_params`` plus a ``Readout`` fit on the host in float64.
    The device path runs in float32.  ``--ensemble`` builds one
    independently-seeded reservoir *per slot* (``stack_params``) and serves
    them all from a single ``vmap``-ed decode trace
    (``ReservoirEngine.from_param_batch``)."""
    import dataclasses

    from repro.core import esn as esn_fn
    from repro.core.params import Readout, stack_params
    from repro.launch.runtime import enable_compile_cache
    from repro.serve import ReservoirEngine, WaveCostModel, cost_key

    enable_compile_cache()
    # Signal long enough for any requested prompt window AND the one-step-
    # ahead continuation the ensemble demo scores against.
    train_t = max(2000, args.prompt_len + args.gen + 512)
    cfg, sig = mso_deployment(args.n, args.seed, train_t)
    u_train, y_train = sig[:-1, None], sig[1:, None]

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_local_mesh
        d, m = (int(v) for v in args.mesh.lower().split("x"))
        if d * m > jax.device_count():
            raise SystemExit(f"--mesh {args.mesh} needs {d * m} devices, "
                             f"have {jax.device_count()}")
        mesh = make_local_mesh(d, m)
        print(f"arena mesh: ({d}, {m}) over (data, model) — slots "
              f"data-parallel, N TP-sharded")

    # Cost fits only transfer within one (backend, n, d_out) — key the model
    # so a stale artifact from another machine/shape shelves instead of fits.
    run_key = cost_key(jax.default_backend(), args.n, 1)
    cost_model = None
    if args.cost_seed:
        # A seed alone enables cost-model *planning* (no per-wave timing
        # sync — the steady-state serving mode); --autotune adds online
        # refinement on top.
        cost_model = WaveCostModel.from_artifact(args.cost_seed, key=run_key)
        mode = ("refining online" if args.autotune
                else "planning only — add --autotune to refine online")
        print(f"cost model seeded with {cost_model.n_observations} offline "
              f"wave timings from {args.cost_seed} ({mode})")
    elif args.autotune:
        cost_model = WaveCostModel(key=run_key)
        print("autotune: cold cost model — learning from this run's "
              "wave timings")
    engine_kw = dict(mesh=mesh, bucket_min=args.bucket,
                     chunk_max=args.chunk_max, autotune=args.autotune,
                     cost_model=cost_model, decode_slo_us=args.decode_slo,
                     decode_wave_tokens=args.decode_wave_tokens,
                     park_host_rows=args.park_host_rows,
                     cold_dir=args.cold_dir,
                     pipeline_depth=args.pipeline_depth,
                     tracker=args.tracker)
    if args.tracker:
        print(f"observability: {args.tracker} (stats() counters derive "
              f"from the same event stream)")
    if args.cold_dir and args.park_host_rows is None:
        raise SystemExit("--cold-dir needs --park-host-rows (the cold tier "
                         "sits behind the host pool)")
    if args.park_host_rows is not None:
        tiers = (f"{args.slots} hot slots -> {args.park_host_rows} host rows"
                 + (f" -> cold dir {args.cold_dir}" if args.cold_dir else ""))
        print(f"tiered session store: {tiers} — capacity is sessions, "
              f"not slots")
    if args.decode_slo is not None:
        print(f"decode-aware planning: SLO {args.decode_slo:.0f} us of "
              f"predicted prefill cost between decode waves "
              f"({args.decode_wave_tokens} tok per fused decode wave)")

    if args.ensemble and args.park_host_rows is not None:
        raise SystemExit("--park-host-rows is incompatible with --ensemble: "
                         "a param-batched engine binds slot i to reservoir "
                         "i, so parked state cannot move slots")
    if args.learn and args.ensemble:
        raise SystemExit("--learn needs the non-ensemble engine (streaming "
                         "refit owns the readout pool; DPG growth builds "
                         "per-session ensembles on drift instead)")
    if args.ensemble:
        batch = [esn_fn.dpg_params(dataclasses.replace(cfg, seed=args.seed + i),
                                   "noisy_golden", sigma=DPG_SIGMA)
                 for i in range(args.slots)]
        params = stack_params(batch)
        readouts = [esn_fn.fit_host(p, u_train, y_train, washout=100).w_out
                    for p in batch]
        readout = Readout(jnp.stack(readouts))
        engine = ReservoirEngine.from_param_batch(
            params, readout=readout,
            ensemble=args.ensemble if args.ensemble != "independent"
            else "off",
            **engine_kw)
        print(f"ensemble mode ({args.ensemble}): {args.slots} independently-"
              f"seeded reservoirs, one vmap-ed decode trace")
        if args.ensemble == "weighted":
            # Validation-RMSE-weighted voting: score each member on a
            # held-out teacher-forced window, weight 1/(rmse^2 + eps).
            v0 = train_t - 400
            rmses = []
            for p, w in zip(batch, readouts):
                pred = np.asarray(esn_fn.predict(
                    p, Readout(w), u_train[v0:]))
                rmses.append(float(np.sqrt(np.mean(
                    (pred - np.asarray(y_train[v0:])) ** 2))))
            weights = [1.0 / (r * r + 1e-9) for r in rmses]
            engine.set_ensemble_weights(weights)
            print("  member val-RMSE: "
                  + ", ".join(f"{r:.3e}" for r in rmses))
    else:
        if args.learn:
            engine_kw.update(learn=True,
                             refit_decay=args.refit_decay,
                             drift_threshold=args.drift_threshold)
        engine = build_reservoir_engine(cfg, sig, slots=args.slots,
                                        **engine_kw)

    if args.ensemble in ("mean", "weighted"):
        # One logical stream, B reservoirs voting: same prompt everywhere,
        # fused closed-loop continuation scored against the true signal.
        for i in range(args.slots):
            engine.submit(i, sig[:args.prompt_len, None])
        engine.flush()
        ys = engine.decode_closed_loop(args.gen)
        fused = np.asarray(ys[0])[:, 0]
        # After prefilling sig[:P] the model predicts one step ahead, so the
        # closed-loop outputs align to sig[P+1 : P+1+G].
        truth = sig[args.prompt_len + 1:args.prompt_len + 1 + args.gen]
        rmse = float(np.sqrt(np.mean((fused - truth) ** 2)))
        print(f"ensemble-{args.ensemble} continuation: {args.gen} tok "
              f"closed loop, rmse vs signal {rmse:.3e} "
              f"(B={args.slots} reservoirs fused into one output)")
        engine.tracker.close()
        return

    if args.learn:
        # Learn-while-serving demo: one live session streams teacher tokens
        # open-loop (decode_step + observe accumulates streaming (G, C)),
        # and every --refit-every tokens a flush(refit=True) wave re-solves
        # its readout from the eigenbasis Gram stats.
        p_len = args.prompt_len
        tokens = min(args.gen * 16, train_t - p_len - 1)
        engine.submit("live", sig[:p_len, None], tenant="live")
        engine.flush()
        errs = []
        for t in range(p_len, p_len + tokens):
            out = engine.decode_step({"live": sig[t, None]})
            errs.append(float(out["live"][0]) - float(sig[t + 1]))
            engine.observe("live", sig[t + 1, None])
            if (t - p_len + 1) % args.refit_every == 0:
                engine.flush(refit=True)
        half = len(errs) // 2
        rm = lambda e: float(np.sqrt(np.mean(np.square(e))))  # noqa: E731
        st = engine.stats()
        print(f"learn-while-serving: {tokens} teacher tok, refit every "
              f"{args.refit_every} — stream RMSE first half "
              f"{rm(errs[:half]):.3e} -> second half {rm(errs[half:]):.3e}")
        print(f"  {st.refit_waves_total} refit waves / "
              f"{st.refit_rows_total} rows in "
              f"{st.refit_us_sum / 1e3:.1f} ms total; drift RMSE "
              f"{engine.drift_rmse('live')}; "
              f"{st.growth_events} DPG growth events")
        engine.tracker.close()
        return

    rng = np.random.default_rng(args.seed)
    # Untimed warmup: compile every prefill-wave shape the timed loop will
    # hit (full waves of `slots` rows plus the final partial wave) and the
    # decode trace, so the reported tok/s measures serving throughput, not
    # XLA compilation — a wave retraces per distinct (B_wave, T_bucket).
    warm_sizes = {min(args.slots, args.sessions)}
    tail = args.sessions % args.slots
    if args.sessions > args.slots and tail:
        warm_sizes.add(tail)
    for wb in sorted(warm_sizes):
        for i in range(wb):
            engine.submit(("warm", i), sig[:args.prompt_len, None])
        engine.flush()
        if args.decode_slo is not None:
            # interleaved decode waves and the open-loop mixed traffic run
            # their own trace shapes — warm those too
            engine.decode_closed_loop(engine.decode_wave_tokens)
            engine.decode_step({("warm", 0): sig[:1]})
        engine.decode_closed_loop(args.gen)
        jax.block_until_ready(engine.states)
        engine.reset()
    # warmup gaps span XLA compiles; the reported decode p50/p95 must not
    engine.clear_decode_gaps()
    # All sessions "arrive" up front and accumulate in the wave scheduler;
    # each flush() admits what fits and runs ONE bucketed batched prefill
    # per wave (async admission replaces the old FIFO-on-add).
    for sid in range(args.sessions):
        lo = int(rng.integers(0, train_t - args.prompt_len - 1))
        engine.submit(sid, sig[lo:lo + args.prompt_len, None])

    done = 0
    prefill_tokens = 0
    decode_tokens = 0
    interleaved_tokens = 0
    t0 = time.time()
    t_prefill = 0.0
    t_decode = 0.0
    interleave = args.decode_slo is not None
    # Under --decode-slo one session stays resident across flushes (a live
    # "chat" stream): the interleaved decode waves are what protect ITS
    # inter-token latency while the other sessions' prefills flood through.
    persistent = 0 if interleave and args.sessions > 1 else None
    seen_ready: set = set()
    while (engine.active_sessions or len(engine.pending)
           or engine.parked_sessions):
        t1 = time.time()
        # wave-batched bucketed prefill of what fits; with --decode-slo the
        # flush itself interleaves decode waves for the sessions that were
        # already ready (their tokens buffer — collected below)
        engine.flush(decode_interleave=interleave)
        jax.block_until_ready(engine.states)  # don't let prefill drain into the decode timer
        t_prefill += time.time() - t1
        # ready (not active): chunk-in-flight sessions hold slots but must
        # not free-run mid-prompt (flush() drains all runnable chunks, so
        # the sets only differ under flush(max_waves=...) partial drains)
        wave = list(engine.ready_sessions)
        if not wave and engine.parked_sessions:
            # a tiered engine may have parked freshly-prefilled sessions
            # before they ever decoded — decode promotes them transparently
            wave = engine.parked_sessions[:args.slots]
        # a resident session re-appears in every wave; count its prompt once
        prefill_tokens += args.prompt_len * len(set(wave) - seen_ready)
        seen_ready.update(wave)
        t1 = time.time()
        if interleave and wave:
            # tokens the interleaved decode waves already generated while
            # the flush drained (decode never fully stalls behind prefill);
            # counted separately — their wall time sits in the flush timer,
            # so folding them into decode_tokens would inflate decode tok/s
            for sid, buf in engine.collect_decoded().items():
                interleaved_tokens += int(buf.shape[0])
                assert np.isfinite(np.asarray(buf)).all()
            # mixed open-loop traffic: a NON-persistent ready session
            # streams a few teacher-forced tokens (decode_step + observe —
            # ground truth replaces the model's feedback between steps).
            # The persistent session stays purely closed-loop: it is the
            # one the interleaved decode waves protect, and injecting
            # free-run tokens into an open-loop stream is exactly what
            # flush(decode_sids=...) exists to prevent.  Fresh wave
            # sessions were not ready at flush start, so the interleave
            # never touched them — their streams are clean.
            open_sid = next((s for s in wave if s != persistent), None)
            if open_sid is not None:
                for t in range(args.prompt_len, args.prompt_len + 4):
                    engine.decode_step({open_sid: sig[t, None]})
                    engine.observe(open_sid, sig[t + 1, None])
                    decode_tokens += 1
        ys = engine.decode_closed_loop(args.gen, sids=wave)
        jax.block_until_ready(engine.states)
        t_decode += time.time() - t1
        decode_tokens += args.gen * len(wave)
        for sid in wave:
            assert np.isfinite(ys[sid]).all()
            if sid == persistent and len(engine.pending):
                continue        # resident until the prefill flood drains
            engine.release(sid)  # queued prompts wait for the next flush wave
            done += 1
    wall = time.time() - t0
    print(f"reservoir n={cfg.n} slots={args.slots}: served {done} sessions "
          f"in {wall:.2f}s ({done / wall:.1f} sessions/s)")
    print(f"  prefill {prefill_tokens} tok in {t_prefill:.2f}s "
          f"({prefill_tokens / max(t_prefill, 1e-9):.0f} tok/s, "
          f"bucketed waves, backend auto-dispatch)")
    print(f"  decode  {decode_tokens} tok in {t_decode:.2f}s "
          f"({decode_tokens / max(t_decode, 1e-9):.0f} tok/s, closed loop)")
    if args.autotune:
        st = engine.stats()
        occ = st.occupancy_mean
        lat = st.wave_us_mean
        print(f"  autotune: {st.waves_total} waves, mean occupancy "
              f"{occ:.2f}, mean wave latency "
              f"{lat / 1e3 if lat else float('nan'):.1f} ms, "
              f"{engine.cost_model.n_observations} cost observations")
        for t_bucket, row in sorted(st.by_bucket.items()):
            us = row["us_sum"] / max(row["timed_waves"], 1)
            print(f"    bucket {t_bucket:>6}: {row['waves']} waves, "
                  f"{row['rows']} rows, {row['tokens']} tok, "
                  f"~{us / 1e3:.1f} ms/wave")
    if args.decode_slo is not None:
        st = engine.stats()
        p50, p95 = st.decode_gap_p50_us, st.decode_gap_p95_us
        fmt = lambda v: "n/a" if v is None else f"{v / 1e3:.1f} ms"  # noqa: E731
        print(f"  decode-aware: {st.decode_interleave_waves} interleaved "
              f"decode waves / {st.decode_waves_total} decode dispatches, "
              f"{interleaved_tokens} tok generated mid-flush; "
              f"inter-token gap p50 {fmt(p50)}, p95 {fmt(p95)} "
              f"(SLO {args.decode_slo / 1e3:.1f} ms of planned prefill)")
    if args.park_host_rows is not None:
        st = engine.stats()
        p95 = st.promote_us_p95
        print(f"  paging: {st.demote_waves} demote / "
              f"{st.promote_waves} promote waves, "
              f"{st.page_rows_total} rows moved, restore p95 "
              f"{'n/a' if p95 is None else f'{p95 / 1e3:.1f} ms'}; "
              f"store now holds {st.sessions_parked} parked sessions "
              f"({st.store})")
    if args.cost_save and engine.cost_model is not None:
        engine.cost_model.to_artifact(args.cost_save)
        print(f"cost model saved: {engine.cost_model.n_observations} "
              f"observations -> {args.cost_save} (reload next run via "
              f"--cost-seed {args.cost_save})")
    if args.snapshot:
        engine.snapshot(args.snapshot)
        print(f"engine snapshot -> {args.snapshot} (resume with "
              f"ReservoirEngine.restore({args.snapshot!r}))")
    engine.tracker.close()      # flush any JSONL trace to disk


# ----------------------------------------------------------------------- lm
def serve_lm(args) -> None:
    from repro.configs import get_config, smoke_config
    from repro.models import lm

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.is_encoder_decoder:
        raise SystemExit("enc-dec serving needs audio frames; use the "
                         "decoder-only archs for this driver")
    params, _ = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab,
                           size=(args.batch, args.prompt_len)).astype(np.int32)

    cache = lm.make_decode_cache(params, cfg, args.batch,
                                 args.prompt_len + args.gen)
    step = jax.jit(lambda p, c, t: lm.decode_step(p, cfg, c, t))

    t0 = time.time()
    logits = None
    for t in range(args.prompt_len):
        logits, cache = step(params, cache, jnp.asarray(prompts[:, t:t + 1]))
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    key = jax.random.PRNGKey(args.seed + 1)
    cur = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out = []
    t0 = time.time()
    for _ in range(args.gen):
        out.append(np.asarray(cur)[:, 0])
        logits, cache = step(params, cache, cur)
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            cur = jax.random.categorical(
                sub, logits[:, -1] / args.temperature)[:, None].astype(
                jnp.int32)
        else:
            cur = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = time.time() - t0

    toks = np.stack(out, 1)
    print(f"arch={cfg.name} batch={args.batch} "
          f"prefill={args.prompt_len}tok in {t_prefill:.2f}s  "
          f"decode={args.gen}tok in {t_decode:.2f}s "
          f"({args.batch * args.gen / max(t_decode, 1e-9):.1f} tok/s)")
    for i in range(min(args.batch, 4)):
        print(f"  req{i}: {toks[i, :12].tolist()}")
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def _wave_tokens(v: str):
    """argparse type for --decode-wave-tokens: an int K, or 'auto' for
    per-flush K-adaptive sizing off the fitted c_dec(B, K) surface."""
    if v == "auto":
        return "auto"
    return int(v)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="recurrentgemma-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # reservoir-engine session serving
    ap.add_argument("--reservoir", action="store_true",
                    help="serve streaming reservoir sessions via "
                         "ReservoirEngine instead of the LM loop")
    ap.add_argument("--sessions", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--n", type=int, default=512,
                    help="reservoir size for --reservoir")
    ap.add_argument("--ensemble", nargs="?", const="independent",
                    choices=["independent", "mean", "weighted"], default=None,
                    help="one independently-seeded reservoir per slot, "
                         "served by a single vmap-over-params decode trace; "
                         "'mean' additionally fuses the per-reservoir "
                         "predictions into one ensemble output, 'weighted' "
                         "fuses with validation-RMSE weights "
                         "(1/(rmse^2+eps) per member)")
    ap.add_argument("--learn", action="store_true",
                    help="learn-while-serving: sessions accumulate streaming "
                         "eigenbasis (G, C) readout stats from the observe() "
                         "teacher path; flush(refit=True) re-solves their "
                         "per-tenant readouts in batched device waves")
    ap.add_argument("--refit-every", type=int, default=64, metavar="T",
                    help="with --learn: teacher tokens between "
                         "flush(refit=True) refit waves")
    ap.add_argument("--refit-decay", type=float, default=1.0,
                    metavar="LAMBDA",
                    help="with --learn: per-token decay of the streaming "
                         "(G, C) window (1.0 = grow forever; <1 lets old "
                         "regimes fade so refits track drift)")
    ap.add_argument("--drift-threshold", type=float, default=None,
                    metavar="RMSE",
                    help="with --learn: when a session's held-out streaming "
                         "RMSE (prequential EWMA) drifts past this, sample a "
                         "fresh DPG reservoir member on-demand and fold it "
                         "into the session's ensemble "
                         "(validation-RMSE-weighted voting)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="place the slot arena on a (data, model) device "
                         "mesh, e.g. 2x1 (slots data-parallel, N TP-sharded)")
    ap.add_argument("--bucket", type=int, default=16,
                    help="smallest prefill bucket; prompt lengths are "
                         "padded up to powers of two for wave batching")
    ap.add_argument("--autotune", action="store_true",
                    help="cost-model wave planning: time every wave, fit "
                         "c(B, T_bucket), and pick wave size/bucket by "
                         "predicted tok/s (two-wave lookahead)")
    ap.add_argument("--cost-seed", default=None, metavar="PATH",
                    help="seed the cost model from a benchmark artifact "
                         "(e.g. artifacts/serve_engine.json); on its own "
                         "enables planning without per-wave timing sync, "
                         "with --autotune it warm-starts the refinement")
    ap.add_argument("--chunk-max", type=int, default=None,
                    help="split prompts longer than this into sequential "
                         "chunk waves (same slot, bit-exact) so one huge "
                         "prompt cannot monopolize the arena")
    ap.add_argument("--decode-wave-tokens", type=_wave_tokens, default=1,
                    metavar="K",
                    help="tokens per interleaved decode wave — each wave is "
                         "ONE fused K-token kernel dispatch (diag step + "
                         "readout + feedback write on-device), so K amortizes "
                         "dispatch overhead and weight traffic at the price "
                         "of K-token reaction latency to new prefill work; "
                         "'auto' re-picks K each flush from the fitted "
                         "c_dec(B, K) surface — largest K whose marginal "
                         "cost/token still improves, capped by --decode-slo")
    ap.add_argument("--pipeline-depth", type=int, default=2, metavar="D",
                    help="in-flight wave window of the pipelined executor: "
                         "up to D dispatched-but-unmaterialized waves may be "
                         "outstanding while the host plans/pages ahead "
                         "(bounded further by --decode-slo via predicted "
                         "wave cost); 0 = strict synchronous flush — block "
                         "after every wave (the bit-exact reference mode)")
    ap.add_argument("--decode-slo", type=float, default=None, metavar="US",
                    help="decode-aware planning: bound the predicted prefill "
                         "cost (microseconds) that may accumulate between a "
                         "ready session's decode waves — flushes interleave "
                         "closed-loop decode waves to hold it (combine with "
                         "--chunk-max so decode can preempt inside a flush)")
    ap.add_argument("--cost-save", default=None, metavar="PATH",
                    help="persist the engine's refined cost model to PATH on "
                         "shutdown (WaveCostModel.to_artifact); reload it "
                         "next run via --cost-seed PATH")
    ap.add_argument("--park-host-rows", type=int, default=None, metavar="R",
                    help="tiered session store: back the slot arena with a "
                         "pinned host-memory pool of R parked-session rows — "
                         "a full arena demotes its LRU idle sessions in "
                         "batched page waves instead of queueing admissions, "
                         "and touching a parked session promotes it back "
                         "transparently")
    ap.add_argument("--cold-dir", default=None, metavar="DIR",
                    help="disk/fsspec cold tier behind the host pool: when "
                         "the pool itself fills, its LRU sessions spill to "
                         "per-session .npz records under DIR (requires "
                         "--park-host-rows)")
    ap.add_argument("--tracker", default=None, metavar="SPEC",
                    help="pluggable observability sink: 'null' or "
                         "'jsonl:PATH' — every prefill/decode/page/refit/"
                         "frontend event streams to PATH as JSON lines (a "
                         "replayable trace; stats() counters derive from "
                         "the same event stream, so they can never "
                         "disagree with it)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="run the serving run under jax.profiler.trace(DIR): "
                         "the device's ops and the serve.* host spans in "
                         "one .xplane.pb under DIR")
    ap.add_argument("--snapshot", default=None, metavar="PATH",
                    help="serialize the whole engine on shutdown (arena + "
                         "parked-session table + scheduler queue + cost "
                         "model); ReservoirEngine.restore(PATH) resumes it")
    args = ap.parse_args()
    with (jax.profiler.trace(args.profile_dir) if args.profile_dir
          else contextlib.nullcontext()):
        if args.reservoir:
            serve_reservoir(args)
        else:
            serve_lm(args)


if __name__ == "__main__":
    main()

"""One run of one benchmark cell on the chip.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: the compile cache in the checkout; the configuration's model from
the seed (``bench.model``); an engine over it behind
``serve.frontend.OpenLoopServer``; every wave shape the cell's traffic can
reach, run once; the cell's own traffic, run in until steady; the measured
window of ``--seconds``; the served tokens checked against the float64
reference (``bench.reference``); one JSON line.  With ``--trace 1`` the
window runs under the profiler and the line carries the per-layer metrics
instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import os
import time


def _since_process_start() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_PROCESS = time.perf_counter() - _since_process_start()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: Seconds of the window that a traced run traces.
TRACE_SECONDS = 10.0


class NoChip(Exception):
    pass


def _program_on_path(root: Path) -> None:
    src = root / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"the program is not in this checkout "
                                f"({src / 'repro'} is missing)")
    sys.path.insert(0, str(src))


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX runs on {info['platform']}")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {info['count']}")
    return info


def recorder_():
    """A ``serve.telemetry.Tracker`` that keeps the engine's prefill and
    decode events with the host time of each."""
    from repro.serve.telemetry import Tracker

    class Recorder(Tracker):
        def __init__(self):
            self.events = []

        def log_wave(self, event):
            kind = event.get("kind")
            if kind in ("prefill", "decode"):
                self.events.append({"t": time.perf_counter(), "kind": kind,
                                    "rows": event["rows"],
                                    "tokens": event["tokens"],
                                    "t_bucket": event.get("t_bucket")})
    return Recorder()


def wave_lengths(traffic: dict, engine_cfg: dict) -> list:
    """One prompt length for each bucket the traffic can reach: the longest
    piece (a prompt, or a ``chunk_max`` chunk of one) that the scheduler
    pads into that bucket."""
    from repro.serve.scheduler import bucket_length
    lo, cap = traffic["prompt"]["xm"], traffic["prompt"]["cap"]
    chunk = engine_cfg.get("chunk_max")
    pieces = set()
    for t in range(lo, cap + 1):
        if chunk is None or t <= chunk:
            pieces.add(t)
        else:
            pieces.add(chunk)
            pieces.add(t % chunk or chunk)
    longest = {}
    for t in pieces:
        b = bucket_length(t, bucket_min=engine_cfg["bucket_min"])
        longest[b] = max(t, longest.get(b, 0))
    return [longest[b] for b in sorted(longest)]


def warm_shapes(engine, lengths, max_rows: int, k_max: int, d_in: int,
                dtype) -> None:
    """Run every wave shape the traffic can reach once, through the public
    path: for each prompt length, waves of 1 to ``max_rows`` rows; then the
    closed-loop waves of 1 to ``k_max`` tokens, drained as the server drains
    them."""
    sids = []
    for t in lengths:
        for rows in range(1, max_rows + 1):
            sids = [("warm", t, rows, i) for i in range(rows)]
            for sid in sids:
                engine.submit(sid, np.zeros((t, d_in), dtype))
            engine.flush()
            if (t, rows) != (lengths[-1], max_rows):
                for sid in sids:
                    engine.release(sid, drop=True)
    for k in range(1, k_max + 1):
        engine.decode_closed_loop(k, sids=sids)
        for arr in engine.collect_decoded().tokens.values():
            for _ in arr:
                pass
    engine.reset()


def run_cell(args, **kw) -> dict:
    """One run; returns the result line's object (see :func:`measure`)."""
    return measure(args, **kw)[0]


def measure(args, *, root: Path = ROOT, require_tpu: bool = True,
            fault=None, rate=None, control: bool = False):
    """One run; returns the result line's object and the run's record.
    ``fault``: a test's hook that breaks the engine before traffic starts.
    ``rate``: another arrival rate than the cell's (the knee sweep).
    ``control``: also put the control in the program's place on the same
    requests and report its gap (``bench.calibrate``)."""
    from . import spec as spec_mod

    spec = spec_mod.Spec(root)
    wl = spec.workload(args.workload)
    cfg = spec.config(wl["config"])
    metrics = spec.metrics(args.workload, trace=bool(args.trace))
    _program_on_path(root)

    import jax
    dev = device_info(wl["chips"], require_tpu)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    compiles = []

    def on_compile(event, sec, **kw):
        if event == COMPILE_EVENT:
            compiles.append(time.perf_counter())
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        return _measure(args, root, require_tpu, fault, rate, control,
                        spec, wl, cfg, metrics, dev, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)


def _measure(args, root, require_tpu, fault, rate, control, spec, wl, cfg,
             metrics, dev, compiles):
    import jax
    from . import client, flops_bytes, loadgen, reference
    from . import model as model_mod

    from repro.serve import ReservoirEngine
    from repro.serve.frontend import OpenLoopServer

    seed = int(args.seed)
    marks = [("start", T_PROCESS), ("imports", time.perf_counter())]
    model, sig = model_mod.build(cfg, seed)
    traffic = dict(wl["traffic"])
    if rate is not None:
        traffic["rate"] = float(rate)
    h_max = traffic["horizon"]["hi"]
    growth = model_mod.closed_loop_growth(
        model, h_max, np.random.default_rng(seed))
    dtype = np.dtype(cfg["dtype"])
    params, readout = model_mod.to_program(model, cfg, dtype)
    eng_cfg = {**cfg["engine"], **wl.get("engine", {})}
    recorder = recorder_()
    engine = ReservoirEngine(
        params, readout=readout, max_slots=eng_cfg["max_slots"],
        bucket_min=eng_cfg["bucket_min"], chunk_max=eng_cfg.get("chunk_max"),
        decode_wave_tokens=eng_cfg["decode_wave_tokens"],
        max_queued=eng_cfg.get("max_queued"), tracker=recorder)
    engine.scheduler.max_wave = eng_cfg["max_wave"]
    marks.append(("model", time.perf_counter()))
    warm_shapes(engine, wave_lengths(traffic, eng_cfg), eng_cfg["max_wave"],
                eng_cfg["decode_wave_tokens"], model.w_in.shape[0], dtype)
    marks.append(("warm", time.perf_counter()))
    if fault is not None:
        fault(engine)

    # The traffic's own seed fixes the schedule (sizes, gaps, prompt
    # offsets), so every run of a cell offers the same work; ``--seed`` picks
    # the model, the signal the prompts are cut from, and the checked sample.
    schedule = np.random.default_rng(int(traffic["seed"]))
    rng = np.random.default_rng([seed, 1])
    sig32 = sig.astype(dtype)

    def prompt_of(req):
        return sig32[req.offset:req.offset + req.length]

    seconds = float(args.seconds)
    run_in = float(wl["run_in_s"])
    grace = float(wl["grace_s"])
    server = OpenLoopServer(client.Annotated(engine))
    profile = {}

    # A traced run traces the first TRACE_SECONDS of the window, and its
    # per-layer metrics read that stretch: a longer trace costs more to
    # reduce than a run may take.
    span = min(seconds, TRACE_SECONDS) if args.trace else seconds

    async def mark_window(t0):
        """Starts the profiler a second before the window, spans the
        measured stretch with the host annotation that the trace reduction
        keys on, and stops the profiler after it."""
        from jax.profiler import TraceAnnotation
        if args.trace:
            await asyncio.sleep(max(0.0, t0 - 1.0 - time.perf_counter()))
            profile["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans only, no Python
            jax.profiler.start_trace(profile["dir"], profiler_options=opts)
        await asyncio.sleep(max(0.0, t0 - time.perf_counter()))
        with TraceAnnotation("bench.window"):
            await asyncio.sleep(max(0.0, t0 + span - time.perf_counter()))
        if args.trace:
            # Off the event loop: writing the trace takes seconds, and the
            # window's requests are still being served.
            await asyncio.get_running_loop().run_in_executor(
                None, jax.profiler.stop_trace)

    async def drive():
        await server.start()
        t_start = time.perf_counter() + 0.05
        t0 = t_start + run_in
        marker = asyncio.ensure_future(mark_window(t0))
        reqs = loadgen.open_loop(schedule, traffic, run_in=run_in,
                                 seconds=seconds, tail=grace,
                                 signal_len=sig.shape[0])
        keep = _sample(rng, [r for r in reqs if 0 <= r.due < seconds],
                       wl["check_requests"])
        recs = await client.open_loop(
            server, reqs, prompt_of, t_zero=t0, window=(t0, t0 + seconds),
            grace=grace, keep={r.index for r in keep})
        await marker
        return recs, t0

    recs, t0 = asyncio.run(drive())
    t_end = t0 + seconds
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    record = {"window": (t0, t0 + span), "seconds": span,
              "cut": t_end + grace, "requests": recs, "events": recorder.events,
              "compiles": [t for t in compiles if t0 <= t < t0 + span],
              "max_slots": eng_cfg["max_slots"], "setup_s": t0 - T_PROCESS,
              "model": flops_bytes.shapes(cfg["model"]), "trace": None,
              "setup_phases": {name: round(t - marks[i][1], 3) for i, (name, t)
                               in enumerate(marks[1:] + [("run_in", t0)])}}
    if "dir" in profile:
        from . import trace_reduce
        record["peaks"] = flops_bytes.peaks(dev["kind"], root)
        record["trace"] = trace_reduce.reduce_dir(profile["dir"])
        shutil.rmtree(profile["dir"], ignore_errors=True)
        if record["trace"] is None and require_tpu:
            raise NoChip("the trace holds no device plane")
    del engine, server, params, readout

    # The check: once the window has closed and the engine is gone, a
    # sample drawn from the seed of the finished requests, the longest
    # among them, against the float64 reference.
    t_check = time.perf_counter()
    measured = [r for r in recs if t0 <= r.due < t_end]
    finished = [r for r in measured if r.done and r.ys is not None]
    served = [np.stack([np.asarray(y, np.float64).reshape(-1)
                        for y in jax.device_get(r.ys)]) for r in finished]
    want = reference.reference_outputs(
        model, [sig[r.offset:r.offset + r.length] for r in finished],
        [r.horizon for r in finished]) if finished else []
    checks = {
        "out_gap": {"value": (reference.gap(served, want) if finished
                              else float("inf")),
                    "limit": float(wl["limits"]["out_gap"])},
        "loop_growth": {"value": growth,
                        "limit": float(cfg["loop_growth_max"])}}
    correct = bool(finished) and all(c["value"] <= c["limit"]
                                     for c in checks.values())
    failed = sum(1 for r in measured if not r.done)
    record["check_s"] = time.perf_counter() - t_check
    if control and finished:
        checks["control_gap"] = {"value": reference.gap(
            reference.control_outputs(
                model, [sig[r.offset:r.offset + r.length] for r in finished],
                [r.horizon for r in finished]), want), "limit": None}

    values = {}
    for m in metrics:
        v = spec.reader(m["name"])(record)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {"correct": correct, "attempted": len(measured), "failed": failed,
           "metrics": values, "device": {**dev, "memory_peak_bytes": peak}}
    trace = record["trace"]
    if trace is not None:
        out["device"]["busy_s"] = trace["busy_s"]
        out["device"]["window_s"] = trace["window_s"]
        out["breakdown"] = trace["breakdown"]
    out["checks"] = checks
    return out, record


def _sample(rng, recs, n: int) -> list:
    """``n`` of ``recs`` drawn by ``rng``, with the longest always in."""
    if not recs:
        return []
    longest = max(recs, key=lambda r: (r.length + r.horizon, -r.index))
    rest = [r for r in recs if r is not longest]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _finite(v: float) -> float:
    return v if np.isfinite(v) else 1e300


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out, record = measure(args)
    except (NoChip, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(f"setup seconds by phase: {record['setup_phases']}",
          file=sys.stderr, flush=True)
    for name, c in out["checks"].items():
        c["value"] = _finite(c["value"])
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

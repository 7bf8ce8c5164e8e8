"""Open-loop load generator for the serving front end (Table: serving SLO).

Closed-loop harnesses (submit, wait, submit) hide queueing delay: the
generator slows down exactly when the server does.  This one is
*open-loop* — arrivals fire on a pre-drawn schedule (Poisson or bursty
ON/OFF) whatever the engine is doing, prompts draw from a bounded-Pareto
(heavy-tailed) length distribution, and every decoded token is stamped as
it leaves the ``OpenLoopServer`` stream.  Reported per offered-load point:

* ``slo_attainment``  — fraction of decoded tokens whose inter-token gap
  (TTFT for the first token, measured from admission) met the decode SLO.
* ``goodput_tps``     — SLO-meeting tokens per second actually delivered,
  vs the offered token rate (the goodput-vs-offered-load curve; the knee
  is where admission control starts paying for itself).
* ``shed``            — requests rejected by the bounded admission queue
  (``AdmissionFull`` — backpressure working as designed, not an error).

The engine runs with a JSONL tracker (``artifacts/serve_loadgen_trace.jsonl``)
so every prefill/decode/frontend event of the run is replayable offline —
the same pluggable-observability seam ``launch/serve.py --tracker`` exposes.
"""
from __future__ import annotations

import asyncio
import os

import numpy as np

from ._util import ARTIFACTS, csv_row, save_artifact

TRACE_PATH = os.path.join(ARTIFACTS, "serve_loadgen_trace.jsonl")


# ---------------------------------------------------------------- arrivals
def poisson_arrivals(rng, rate_rps: float, n: int) -> np.ndarray:
    """n arrival instants (seconds from start) of a Poisson process."""
    return np.cumsum(rng.exponential(1.0 / rate_rps, size=n))


def bursty_arrivals(rng, rate_rps: float, n: int, *, burst_factor: float = 4.0,
                    p_on: float = 0.3) -> np.ndarray:
    """Markov-modulated Poisson: ON periods fire at ``burst_factor`` x the
    mean rate, OFF periods at the complementary rate that keeps the
    long-run average at ``rate_rps`` — same offered load, bursty shape."""
    on_rate = burst_factor * rate_rps
    off_rate = max(rate_rps * (1.0 - burst_factor * p_on) / (1.0 - p_on),
                   0.05 * rate_rps)
    gaps = np.where(rng.random(n) < p_on,
                    rng.exponential(1.0 / on_rate, size=n),
                    rng.exponential(1.0 / off_rate, size=n))
    return np.cumsum(gaps)


def pareto_lengths(rng, n: int, *, xm: int = 12, alpha: float = 1.3,
                   cap: int = 192) -> np.ndarray:
    """Bounded-Pareto prompt lengths: mostly short, a heavy tail of long
    prompts (the mix that makes same-bucket wave batching interesting)."""
    raw = xm * (1.0 + rng.pareto(alpha, size=n))
    return np.clip(raw.astype(int), xm, cap)


# ------------------------------------------------------------------ driver
async def _drive(engine, arrivals, prompts, n_decode: int,
                 slo_s: float, ttft_slo_s: float):
    from repro.serve import AdmissionFull, OpenLoopServer

    server = OpenLoopServer(engine, max_waves_per_cycle=2)
    await server.start()
    t0 = asyncio.get_running_loop().time()
    handles, shed = [], 0

    async def _submit_all():
        nonlocal shed
        for i, (t_at, (u, y)) in enumerate(zip(arrivals, prompts)):
            delay = t0 + t_at - asyncio.get_running_loop().time()
            if delay > 0:
                await asyncio.sleep(delay)
            try:
                h = await server.submit(f"r{i}", u, y, n_decode=n_decode)
                handles.append(h)
            except AdmissionFull:
                shed += 1

    async def _consume(h):
        return [tok async for tok in h]

    await _submit_all()
    await server.drain()
    streams = [await _consume(h) for h in handles]
    wall_s = asyncio.get_running_loop().time() - t0

    met = total = 0
    ttfts = []
    for h, toks in zip(handles, streams):
        prev = h.t_admitted
        for j, tok in enumerate(toks):
            gap = tok.t_wall - prev
            target = ttft_slo_s if j == 0 else slo_s
            met += gap <= target
            total += 1
            prev = tok.t_wall
        if toks:
            ttfts.append(toks[0].t_wall - h.t_admitted)
    return {"completed": len(handles), "shed": shed, "tokens": total,
            "tokens_met": met,
            "slo_attainment": met / total if total else float("nan"),
            "goodput_tps": met / wall_s if wall_s > 0 else 0.0,
            "ttft_p95_s": (float(np.percentile(ttfts, 95))
                           if ttfts else float("nan")),
            "wall_s": wall_s}


def _build_engine(quick: bool):
    from repro.core import esn as esn_fn
    from repro.core.esn import ESNConfig
    from repro.data.signals import mso_series
    from repro.serve import ReservoirEngine

    cfg = ESNConfig(n=64 if quick else 128, d_in=1, d_out=1,
                    spectral_radius=0.9, leak=0.85, ridge_alpha=1e-6,
                    seed=7)
    sig = mso_series(3, 1201)
    u, y = sig[:-1, None], sig[1:, None]
    params = esn_fn.diag_params(cfg)
    readout = esn_fn.fit_host(params, u[:600], y[:600], washout=50)
    eng = ReservoirEngine(params, readout=readout,
                          max_slots=4 if quick else 8,
                          max_queued=16 if quick else 64,
                          tracker=f"jsonl:{TRACE_PATH}")
    return eng, u, y


def main(quick: bool = False):
    rng = np.random.default_rng(42)
    # Stale-trace removal must precede engine construction: the JSONL
    # tracker opens its file handle in the engine constructor.
    os.makedirs(ARTIFACTS, exist_ok=True)
    if os.path.exists(TRACE_PATH):
        os.remove(TRACE_PATH)
    eng, u, y = _build_engine(quick)
    n_req = 24 if quick else 120
    n_decode = 8 if quick else 16
    # Generous CPU-CI SLOs — the curve shape, not the absolute numbers, is
    # the point; launch/serve.py lets operators pass real targets.
    slo_s, ttft_slo_s = 0.25, 2.0

    prompts = []
    lens = pareto_lengths(rng, n_req, cap=96 if quick else 192)

    # Warm the compile caches — one prefill per distinct bucket plus the
    # decode path — so the first load point measures serving, not XLA
    # compilation (a mid-run multi-second compile stall floods the bounded
    # queue and reads as shed/SLO misses that no steady state would show).
    from repro.serve import bucket_length
    for b in sorted({bucket_length(int(t)) for t in lens}):
        t = min(int(b), 900)
        eng.submit(f"warm{b}", u[:t])
    eng.flush()
    eng.decode_closed_loop(2)
    eng.collect_decoded()
    eng.reset()
    for t in lens:
        off = int(rng.integers(0, 900 - int(t)))
        prompts.append((u[off:off + t], None))

    # Offered-load sweep: requests/sec low -> past saturation, plus one
    # bursty point at the middle rate.
    rates = [4.0, 16.0] if quick else [4.0, 12.0, 32.0]
    rows, art = [], {"points": [], "slo_s": slo_s, "ttft_slo_s": ttft_slo_s,
                     "n_req": n_req, "n_decode": n_decode}
    for shape, rate in ([("poisson", r) for r in rates]
                        + [("bursty", rates[len(rates) // 2])]):
        arr = (poisson_arrivals(rng, rate, n_req) if shape == "poisson"
               else bursty_arrivals(rng, rate, n_req))
        res = asyncio.run(_drive(eng, arr, prompts, n_decode,
                                 slo_s, ttft_slo_s))
        res.update(shape=shape, offered_rps=rate,
                   offered_tps=rate * n_decode)
        art["points"].append(res)
        tag = f"{shape}@{rate:g}rps"
        rows.append(csv_row(f"serve.openloop.goodput_tps.{tag}",
                            res["goodput_tps"],
                            f"attain={res['slo_attainment']:.3f} "
                            f"shed={res['shed']}"))
        eng.reset()
    # The gated scalar: worst-case SLO attainment across the sweep (NaN if
    # nothing completed — trajectory.py NaN-guards it).
    attain = [p["slo_attainment"] for p in art["points"]]
    worst = (float(np.nanmin(attain))
             if np.isfinite(attain).any() else float("nan"))
    art["slo_attainment_worst"] = worst
    rows.append(csv_row("serve.openloop.slo_attainment", worst,
                        f"worst of {len(attain)} load points"))
    save_artifact("serve_loadgen.json", art)
    if hasattr(eng.tracker, "close"):
        eng.tracker.close()         # flush the JSONL trace to disk
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", default=True)
    args = ap.parse_args()
    for r in main(quick=args.quick):
        print(r)

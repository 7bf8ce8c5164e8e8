"""Readings for the limits and the rate: many runs of one cell in one
process, so that they share its compiled programs.

    python3 -m bench.calibrate --workload <cell> --seeds 12 --control 3 \\
        --seconds 5            # the program's gap on each seed, the control's
    python3 -m bench.calibrate --workload <cell> --rates 10,20,40 \\
        --seconds 10           # the knee sweep of an open-loop cell

Each run prints one JSON line.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from . import run


def backlog(record) -> dict:
    """Whether the queue grew through the window: admitted-but-unfinished
    requests at the first quarter and at the end of the window, and the
    median time to first token of the window's first and second halves;
    the most requests open at once (sent, not yet finished) and the most rows
    of one decode wave, the sessions that the arena has to hold."""
    t0, t1 = record["window"]
    reqs = record["requests"]

    def open_at(t):
        return sum(1 for r in reqs if r.submit is not None and r.submit <= t
                   and not (r.recv and len(r.recv) >= r.horizon
                            and r.recv[-1] <= t))

    def ttft(lo, hi):
        v = [r.recv[0] - r.due for r in reqs if lo <= r.due < hi and r.recv]
        return float(np.median(v)) * 1e3 if v else None
    def peak_open():
        edges = sorted([(r.submit, 1) for r in reqs if r.submit is not None]
                       + [(r.recv[-1], -1) for r in reqs
                          if r.submit is not None and r.done])
        return max(np.cumsum([d for _, d in edges]), default=0)
    mid = (t0 + t1) / 2
    return {"open_q1": open_at(t0 + (t1 - t0) / 4), "open_end": open_at(t1),
            "peak_open": int(peak_open()),
            "peak_decode_rows": max((e["rows"] for e in record["events"]
                                     if e["kind"] == "decode"), default=0),
            "ttft_med_first_ms": ttft(t0, mid),
            "ttft_med_second_ms": ttft(mid, t1),
            "shed": sum(1 for r in reqs if r.shed),
            "unfinished": sum(1 for r in reqs if not r.done)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, default=0)
    ap.add_argument("--control", type=int, default=0,
                    help="how many of the seeds also run the control")
    ap.add_argument("--first-seed", type=int, default=2**31 + 12345)
    ap.add_argument("--rates", default="")
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    runs = [("seed", args.first_seed + 7919 * i) for i in range(args.seeds)]
    runs += [("rate", float(r)) for r in args.rates.split(",") if r]
    for i, (kind, v) in enumerate(runs):
        seed = v if kind == "seed" else args.first_seed + 104729 * i
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=args.trace)
        out, rec = run.measure(ns, rate=v if kind == "rate" else None,
                               control=kind == "seed" and i < args.control)
        line = {"seed": seed, "rate": v if kind == "rate" else None,
                "correct": out["correct"], "attempted": out["attempted"],
                "failed": out["failed"],
                "checks": {k: c["value"] for k, c in out["checks"].items()},
                "metrics": {k: m["value"] for k, m in out["metrics"].items()},
                "device": out["device"], "check_s": rec.get("check_s"),
                "setup_phases": rec["setup_phases"]}
        if kind == "rate":
            line["backlog"] = backlog(rec)
        if "breakdown" in out:
            line["breakdown"] = out["breakdown"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The program's own host spans in a profiler trace: where the serving loop's
host time goes, and what the host was doing while the device sat idle.

The program marks its serving loop with ``serve.*`` host spans
(``repro.serve.telemetry.span``): ``serve.cycle`` (one iteration of
``OpenLoopServer``) holding ``serve.flush`` (``serve.plan``,
``serve.wave``), ``serve.decode`` and ``serve.route``; ``serve.wait`` while
nothing is runnable; ``serve.submit`` per request; ``serve.dispatch`` around
each jitted call and ``serve.block`` around each host wait on the device.
Inside the harness's ``bench.window``, on the clock of the device's ``XLA
Ops`` line, :func:`reduce` gives:

* ``self_s``: each span's duration less what its children cover, summed by
  name (``none``: the host in no ``serve.*`` span, as when the clients'
  coroutines run); ``count``: the spans of each name inside the window;
* ``idle_by_span``: the device's idle seconds, each attributed to the
  innermost ``serve.*`` span covering it, or ``none``; ``idle_none_share``
  is the part under no span, in % of all idle;
* ``idle_in_loop_share``: device-idle time inside ``serve.cycle`` spans, in
  % of the window: idle that the loop's own host code causes, as against
  ``serve.wait`` (nothing to serve);
* ``cycle_p95_ms``: p95 duration of the cycles that hold a ``serve.flush``
  or ``serve.decode``;
* ``route_us_per_token``: ``serve.route`` time over the tokens it routed
  (its ``tokens`` attribute);
* ``host_block_share``: ``serve.block`` time inside cycles, in % of cycle
  time;
* ``queue_wait_p50_ms``: per request submitted in the window, from the end
  of its ``serve.submit`` to the start of the first ``serve.wave`` whose
  ``sids`` hold it; the median.

The harness's own reduction (``bench.trace_reduce``) reads only its
``engine.*`` spans, and ``bench.run`` deletes the trace once reduced.  Run
as a script, this module makes traced runs of a cell with the program's
spans reduced as well (:func:`reduce_dir` in the harness's place), and
times each serving cycle on the host clock, traced or not::

    python3 -m bench.spans --workload <cell> --seconds <s> --trace <0|1> \\
        --seed <a> [--seed <b> ...]

It prints one JSON line a seed.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import heapq
import json
import sys
import time
from pathlib import Path

import numpy as np

from . import trace_reduce

PREFIX = "serve."
CYCLE, ROUTE, BLOCK = "serve.cycle", "serve.route", "serve.block"
SUBMIT, WAVE = "serve.submit", "serve.wave"
#: A cycle that did work holds one of these.
WORK = ("serve.flush", "serve.decode")
NONE = "none"
#: Seconds past the traced stretch left out of the untraced rest of a
#: traced run: the profiler writes its trace then.
GAP_S = 5.0


def load(path) -> list:
    """``(name, start_ns, end_ns, attributes)`` of each ``serve.*`` event on
    the host planes of the trace at ``path``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def innermost(spans, w0, w1) -> list:
    """``[(start, end, name)]``: ``[w0, w1]`` cut where any span starts or
    ends, each piece named by the innermost span covering it (the latest
    started; of two started together, the shorter), or :data:`NONE`."""
    cuts = sorted({w0, w1} | {t for _, a, b, _ in spans for t in (a, b)
                              if w0 < t < w1})
    order = sorted(spans, key=lambda s: s[1])
    active, i, out = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while i < len(order) and order[i][1] <= a:
            name, s, e, _ = order[i]
            heapq.heappush(active, (-s, e, i, name))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        name = active[0][3] if active else NONE
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def overlap_by_name(intervals, pieces) -> collections.Counter:
    """Nanoseconds of the sorted, disjoint ``intervals`` that fall in each
    name's ``pieces`` (sorted, disjoint ``(start, end, name)``)."""
    out = collections.Counter()
    j = 0
    for a, b in intervals:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, name = pieces[k]
            out[name] += min(b, e) - max(a, s)
            k += 1
    return out


def _containing(outer, inner) -> list:
    """For each of ``inner``, the index in ``outer`` (disjoint, sorted by
    start) of the interval holding it, or None."""
    starts = [o[1] for o in outer]
    out = []
    for _, a, b, _ in inner:
        i = bisect.bisect_right(starts, a) - 1
        out.append(i if i >= 0 and b <= outer[i][2] else None)
    return out


def _sids(value) -> list:
    """A ``sids`` attribute (``|``-joined; one sid comes back as a number)
    as strings."""
    return str(value).split("|")


def reduce(devices, window, spans, top: int = 10) -> dict:
    """The quantities of the module docstring.  ``devices``: as
    ``trace_reduce.load`` gives them; ``window``: ``(start_ns, end_ns)``;
    ``spans``: as :func:`load` gives them.  A quantity with nothing to read
    is None."""
    w0, w1 = window
    window_s = (w1 - w0) * 1e-9
    inside = [s for s in spans if s[1] >= w0 and s[2] <= w1]
    clipped = [(n, max(a, w0), min(b, w1), at) for n, a, b, at in spans
               if b > w0 and a < w1]
    pieces = innermost(clipped, w0, w1)
    self_s = collections.Counter()
    for a, b, name in pieces:
        self_s[name] += (b - a) * 1e-9
    cycles = sorted((s for s in inside if s[0] == CYCLE),
                    key=lambda s: s[1])
    in_cycles = [(a, b, CYCLE) for _, a, b, _ in sorted(
        (s for s in clipped if s[0] == CYCLE), key=lambda s: s[1])]

    idle = collections.Counter()
    idle_in_loop = 0
    for lines in devices:
        ops = trace_reduce.clip(lines.get(trace_reduce.OPS_LINE, []), w0, w1)
        gaps = trace_reduce.gaps(trace_reduce.union(
            (a, b) for _, a, b in ops), w0, w1)
        idle.update(overlap_by_name(gaps, pieces))
        idle_in_loop += overlap_by_name(gaps, in_cycles)[CYCLE]
    n_dev = max(len(devices), 1)
    idle_s = {k: v * 1e-9 / n_dev for k, v in idle.items()}
    idle_total = sum(idle_s.values())

    worked = set()
    for i in _containing(cycles, [s for s in inside if s[0] in WORK]):
        if i is not None:
            worked.add(i)
    cycle_ms = [(cycles[i][2] - cycles[i][1]) * 1e-6 for i in sorted(worked)]
    blocks = [s for s in inside if s[0] == BLOCK]
    block_ns = sum(b[2] - b[1] for b, i in zip(blocks, _containing(
        cycles, blocks)) if i is not None)
    cycle_ns = sum(c[2] - c[1] for c in cycles)
    routes = [s for s in inside if s[0] == ROUTE]
    routed = sum(int(r[3].get("tokens", 0)) for r in routes)

    return {
        "window_s": window_s,
        "self_s": dict(self_s.most_common()),
        "count": dict(collections.Counter(s[0] for s in inside)),
        "idle_by_span": [[n, s] for n, s in sorted(
            idle_s.items(), key=lambda kv: -kv[1])[:top]],
        "idle_none_share": (100.0 * idle_s.get(NONE, 0.0) / idle_total
                            if devices and idle_total > 0 else None),
        "idle_in_loop_share": (100.0 * idle_in_loop * 1e-9 / n_dev
                               / window_s if devices else None),
        "cycle_p95_ms": (float(np.percentile(cycle_ms, 95)) if cycle_ms
                         else None),
        "route_us_per_token": (sum(r[2] - r[1] for r in routes) * 1e-3
                               / routed if routed else None),
        "host_block_share": (100.0 * block_ns / cycle_ns if cycle_ns
                             else None),
        "queue_wait_p50_ms": queue_wait_p50_ms(spans, w0, w1),
    }


def queue_wait_p50_ms(spans, w0, w1):
    """Median over the requests whose ``serve.submit`` ended in the window
    of the time to the start of the first later ``serve.wave`` holding
    them; None when no such request reached a wave in the trace."""
    submitted = {str(s[3].get("sid")): s[2] for s in spans
                 if s[0] == SUBMIT and w0 <= s[2] < w1}
    waits = []
    for _, a, _, attrs in sorted((s for s in spans if s[0] == WAVE),
                                 key=lambda s: s[1]):
        for sid in _sids(attrs.get("sids", "")):
            end = submitted.get(sid)
            if end is not None and a >= end:
                waits.append((a - end) * 1e-6)
                del submitted[sid]
    return float(np.median(waits)) if waits else None


def reduce_dir(directory):
    """``trace_reduce.reduce_dir`` with the program's spans as well:
    ``summary["program"]`` holds :func:`reduce`'s quantities and
    ``summary["breakdown"]["idle_by_span"]`` its idle attribution.  None
    when the trace holds no device plane."""
    paths = sorted(Path(directory).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    devices, harness = trace_reduce.load(paths[-1])
    if not devices:
        return None
    summary = trace_reduce.summarize(devices, harness)
    window = next((a, b) for n, a, b in harness if n == trace_reduce.WINDOW)
    program = reduce(devices, window, load(paths[-1]))
    summary["breakdown"]["idle_by_span"] = program.pop("idle_by_span")
    summary["program"] = program
    return summary


# ---------------------------------------------------------------- the script
def _p95_ms(values):
    return float(np.percentile(values, 95)) * 1e3 if len(values) else None


def _stretches(record, cycles, seconds: float) -> dict:
    """``itl_p95_ms`` and the host-timed p95 of the cycles that did work,
    over the traced stretch and over the rest of the ``seconds`` window
    (from :data:`GAP_S` past the traced stretch); untraced, over the whole
    window."""
    t0, t1 = record["window"]
    t_end = t0 + seconds
    parts = {"all": (t0, t_end)}
    if record["trace"] is not None:
        parts = {"traced": (t0, t1), "after": (t1 + GAP_S, t_end)}
    out = {}
    for name, (a, b) in parts.items():
        gaps = [g for r in record["requests"] if a <= r.due < b
                for g in np.diff(r.recv)]
        out[name] = {
            "itl_p95_ms": _p95_ms(gaps),
            "cycle_host_p95_ms": _p95_ms([d for t, d in cycles
                                          if a <= t < b])}
    return out


def run_seed(args, seed: int, *, root=None, require_tpu: bool = True
             ) -> dict:
    """One run of ``bench.run.measure`` with the program's spans reduced
    (traced) and each serving cycle that did work timed on the host."""
    from . import run as run_mod
    from . import spec as spec_mod
    root = run_mod.ROOT if root is None else Path(root)
    run_mod._program_on_path(root)
    from repro.serve.frontend import OpenLoopServer

    cycles = []
    plain_cycle, plain_reduce = OpenLoopServer._cycle, trace_reduce.reduce_dir

    def timed_cycle(server):
        t = time.perf_counter()
        worked = plain_cycle(server)
        if worked:
            cycles.append((t, time.perf_counter() - t))
        return worked

    OpenLoopServer._cycle = timed_cycle
    trace_reduce.reduce_dir = reduce_dir
    try:
        out, record = run_mod.measure(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds,
            trace=args.trace), root=root, require_tpu=require_tpu)
    finally:
        OpenLoopServer._cycle = plain_cycle
        trace_reduce.reduce_dir = plain_reduce
    spec = spec_mod.Spec(root)
    line = {"seed": seed, "trace": args.trace, "correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "itl_p95_ms": spec.reader("itl_p95_ms")(record),
            "ttft_p50_ms": spec.reader("ttft_p50_ms")(record),
            "setup_s": record["setup_s"],
            "stretches": _stretches(record, cycles, args.seconds)}
    if record["trace"] is not None:
        line["busy_s"] = record["trace"]["busy_s"]
        line["program"] = record["trace"]["program"]
        line["idle_by_span"] = record["trace"]["breakdown"]["idle_by_span"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    for seed in args.seed:
        print(json.dumps(run_seed(args, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The window is the host span ``bench.window`` that the harness opens at the
first due request and closes at the end of the measured seconds.  Inside it:

* busy: the union of the intervals in which an operation ran on each
  device (a plane with an ``XLA Ops`` line), averaged over the devices;
  idle is the rest of the window;
* op time by name and program time by name, clipped to the window.  A
  program is named as jit names it, without the ``jit_`` prefix and the
  hash (``closed_loop_fused``); an op by its program and its HLO
  instruction (``closed_loop_fused/%closed_loop_fused.1``), with
  ``kernel`` appended where it is a Pallas kernel (``tpu_custom_call``);
* ``breakdown``: the ten operations that took most time, and the ten
  longest idle gaps, each named by the host span of the harness
  (``engine.*``) that covered most of it, or ``unannotated``.
"""
from __future__ import annotations

import bisect
import collections
from pathlib import Path

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "engine."


def program_name(module: str) -> str:
    """``jit_closed_loop_fused(1746...)`` -> ``closed_loop_fused``."""
    name = module.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(hlo: str, program: str) -> str:
    """An ``XLA Ops`` event (the HLO instruction's text) -> its program and
    instruction, and ``kernel`` for a Pallas call."""
    name = f"{program}/{hlo.split(' = ', 1)[0]}"
    return f"{name} kernel" if '"tpu_custom_call"' in hlo else name


def label_ops(ops, modules):
    """Name each op after the program whose interval holds its start."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for hlo, a, b in ops:
        i = bisect.bisect_right(starts, a) - 1
        prog = (program_name(modules[i][0])
                if i >= 0 and a < modules[i][2] else "?")
        out.append((op_name(hlo, prog), a, b))
    return out


def load(path):
    """(devices, host_spans): per device plane a dict of line name ->
    [(name, start_ns, end_ns)] with ops and programs named as above, and
    the host spans of the harness."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in data.planes:
        lines = {line.name: [(e.name, e.start_ns, e.end_ns)
                             for e in line.events]
                 for line in plane.lines
                 if line.name in (OPS_LINE, MODULES_LINE)}
        if OPS_LINE in lines:
            modules = lines.get(MODULES_LINE, [])
            devices.append({
                OPS_LINE: label_ops(lines[OPS_LINE], modules),
                MODULES_LINE: [(program_name(n), a, b)
                               for n, a, b in modules]})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW or e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
    return devices, spans


def union(intervals):
    """Sorted, merged intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(events, w0, w1):
    return [(n, max(a, w0), min(b, w1)) for n, a, b in events
            if b > w0 and a < w1]


def gaps(busy, w0, w1):
    """Idle intervals of the window between merged busy intervals."""
    out, t = [], w0
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < w1:
        out.append((t, w1))
    return out


def name_gap(gap, spans):
    """The host span that covers most of ``gap``, if it covers at least half
    of it; else ``unannotated`` (the host was in none of the harness's calls,
    as when the server waits for the next request)."""
    a, b = gap
    best, best_overlap = "unannotated", (b - a) / 2
    for name, s, e in spans:
        overlap = min(b, e) - max(a, s)
        if overlap >= best_overlap:
            best, best_overlap = name, overlap
    return best


def summarize(devices, spans, top: int = 10) -> dict:
    wins = [(s, e) for n, s, e in spans if n == WINDOW]
    if not wins or not devices:
        raise ValueError("trace holds no window span or no device plane")
    w0, w1 = wins[0]
    window_s = (w1 - w0) * 1e-9
    host = [s for s in spans if s[0] != WINDOW]
    ops_s, modules_s = collections.Counter(), collections.Counter()
    busy_s, idle = 0.0, []
    for lines in devices:
        ops = clip(lines.get(OPS_LINE, []), w0, w1)
        for name, a, b in ops:
            ops_s[name] += (b - a) * 1e-9
        for name, a, b in clip(lines.get(MODULES_LINE, []), w0, w1):
            modules_s[name] += (b - a) * 1e-9
        merged = union((a, b) for _, a, b in ops)
        busy_s += sum(b - a for a, b in merged) * 1e-9
        idle += gaps(merged, w0, w1)
    busy_s /= len(devices)
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": window_s, "busy_s": busy_s,
        "ops_s": dict(ops_s), "modules_s": dict(modules_s),
        "breakdown": {
            "device_ops": [[n, s] for n, s in ops_s.most_common(top)],
            "idle_gaps": [[name_gap(g, host), (g[1] - g[0]) * 1e-9]
                          for g in idle[:top]]}}


def reduce_dir(directory):
    """The summary of the trace under ``directory``; None when it holds no
    device plane (a run on the CPU, which only the tests make)."""
    paths = sorted(Path(directory).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    devices, spans = load(paths[-1])
    return summarize(devices, spans) if devices else None

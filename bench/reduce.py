"""Arithmetic the metric readers share: percentiles, and the engine's
events and the trace's times inside the window."""
from __future__ import annotations

import numpy as np

from . import flops_bytes

#: The engine's jitted closed-loop step, as the trace names its program; it
#: holds one Pallas kernel (``bench.trace_reduce`` marks it ``kernel``).
DECODE_PROGRAM = "closed_loop_fused"


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, float), 95))


def in_window(rec: dict, kind: str) -> list:
    t0, t1 = rec["window"]
    return [e for e in rec["events"] if e["kind"] == kind and t0 <= e["t"] < t1]


def cutoff(rec: dict) -> float:
    """When the harness stopped waiting: the end of the measured seconds
    plus grace, also where a traced run's metrics read a shorter stretch."""
    return rec["cut"]


def program_seconds(rec: dict, program: str):
    """Device seconds of ``program`` in the traced window; None without a
    trace or without such a program."""
    trace = rec.get("trace")
    if trace is None:
        return None
    return trace["modules_s"].get(program) or None


def kernel_seconds(rec: dict, program: str):
    """Device seconds of the Pallas kernel inside ``program``."""
    trace = rec.get("trace")
    if trace is None:
        return None
    return sum(s for name, s in trace["ops_s"].items()
               if name.startswith(program + "/")
               and name.endswith(" kernel")) or None


def decode_least_seconds(rec: dict) -> float:
    return sum(flops_bytes.least_seconds(
        *flops_bytes.decode_call(rec["model"], e["rows"], e["tokens"]),
        rec["peaks"]) for e in in_window(rec, "decode"))


def share(num, den):
    if num is None or den is None or den <= 0 or num <= 0:
        return None
    return 100.0 * num / den

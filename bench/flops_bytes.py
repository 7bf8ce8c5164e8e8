"""The least operations and bytes of the model's work, from its shapes.

Counted the same way whatever implements the work, with no padding: a step
of the recurrence costs 2 FLOP per real eigenvalue and 8 per conjugate pair
(complex multiply and add); the drive ``u @ w_in`` 2 D_in per state
coordinate; the readout 2 (N + 1) D_out.  Arrays are float32 (4 bytes).
"""
from __future__ import annotations

import json
from pathlib import Path

WORD = 4


def shapes(model_cfg: dict) -> dict:
    """The sizes the counts need, from a configuration's ``model``."""
    from .model import n_real_for
    n = model_cfg["n"]
    nr = n_real_for(n)
    return {"n": n, "n_real": nr, "n_pair": (n - nr) // 2,
            "d_in": model_cfg["d_in"], "d_out": model_cfg["d_out"]}


def peaks(kind: str, root: Path) -> dict:
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def recurrence_flops(s: dict) -> int:
    return 2 * s["n_real"] + 8 * s["n_pair"]


def readout_flops(s: dict) -> int:
    return 2 * (s["n"] + 1) * s["d_out"]


def step_flops(s: dict) -> int:
    """One prompt step of one row: recurrence and drive."""
    return recurrence_flops(s) + 2 * s["d_in"] * s["n"]


def token_flops(s: dict) -> int:
    """One closed-loop token of one row: recurrence, drive, readout."""
    return step_flops(s) + readout_flops(s)


def weight_bytes(s: dict) -> int:
    return WORD * (s["n"] + s["d_in"] * s["n"] + (s["n"] + 1) * s["d_out"])


def decode_call(s: dict, rows: int, tokens: int):
    """(FLOP, bytes) of one closed-loop wave: ``rows`` live rows advance
    ``tokens`` tokens.  Bytes: the weights once, each row's state and last
    output read and written, every token written."""
    flops = rows * tokens * token_flops(s)
    moved = (weight_bytes(s) + rows * WORD * 2 * (s["n"] + s["d_out"])
             + rows * tokens * WORD * s["d_out"])
    return flops, moved


def least_seconds(flops: float, moved: float, peak: dict) -> float:
    """The roofline bound: the larger of compute time at the peak rate and
    memory time at the peak bandwidth."""
    return max(flops / peak["flops_per_s"], moved / peak["hbm_bytes_per_s"])

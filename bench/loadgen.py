"""Traffic from a workload's parameters.

The generators are those of the repository's open-loop load generator
(Poisson arrivals, bounded-Pareto prompt lengths), drawn here by quantile in
blocks: every ``BLOCK`` consecutive requests carry the same multiset of
prompt lengths, horizons and gaps, in an order the generator's seed picks.
A workload gives that seed (``traffic.seed``), so every run of a cell
offers the same schedule.
"""
from __future__ import annotations

import dataclasses

import numpy as np


#: Requests per block of the same multiset of sizes.
BLOCK = 64


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _blocked(rng, n: int, values: np.ndarray) -> np.ndarray:
    """``n`` values: the block's multiset ``values`` again and again, each
    block in its own order."""
    reps = -(-n // len(values))
    return np.concatenate([rng.permutation(values)
                           for _ in range(reps)])[:n]


def pareto_lengths(n: int, *, xm: int, alpha: float, cap: int) -> np.ndarray:
    """Bounded Pareto: ``xm (1 - p)^(-1/alpha)`` clipped to ``cap``."""
    raw = xm * (1.0 - _quantiles(n)) ** (-1.0 / alpha)
    return np.clip(raw.astype(np.int64), xm, cap)


def log_uniform(n: int, *, lo: int, hi: int) -> np.ndarray:
    return np.rint(np.exp(np.log(lo) + _quantiles(n) * np.log(hi / lo))
                   ).astype(np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """Inter-arrival gaps of a Poisson process of ``rate`` per second."""
    return -np.log(1.0 - _quantiles(n)) / rate


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due: float            # seconds from the window's start (negative: run-in)
    length: int           # prompt steps
    horizon: int          # forecast tokens
    offset: int           # where the prompt starts in the signal


def open_loop(rng, traffic: dict, *, run_in: float, seconds: float,
              tail: float, signal_len: int):
    """Poisson arrivals at ``traffic["rate"]`` from ``-run_in`` to
    ``seconds + tail``."""
    rate = float(traffic["rate"])
    n = int(np.ceil(rate * (run_in + seconds + tail)))
    due = np.cumsum(_blocked(rng, n, exponential_gaps(BLOCK, rate))) - run_in
    lengths = _blocked(rng, n, pareto_lengths(BLOCK, **traffic["prompt"]))
    horizons = _blocked(rng, n, log_uniform(BLOCK, **traffic["horizon"]))
    offs = rng.integers(0, signal_len - lengths - 1)
    return [Request(i, float(due[i]), int(lengths[i]), int(horizons[i]),
                    int(offs[i])) for i in range(n)]

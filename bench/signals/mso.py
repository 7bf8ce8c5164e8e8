"""MSO-k: k superimposed sines (the paper's multiple superimposed oscillator
task).  ``rng`` picks the phase offset along the signal, so every seed sees
another stretch of the same deterministic signal."""
from __future__ import annotations

import numpy as np

#: The paper's MSO-k frequencies; MSO-k takes the first k.
FREQS = (0.2, 0.331, 0.42, 0.51, 0.63, 0.74, 0.85, 0.97, 1.08, 1.19, 1.27,
         1.32)


def generate(rng, length: int, *, n_sines: int) -> np.ndarray:
    t = np.arange(length, dtype=np.float64) + float(rng.integers(0, 1 << 20))
    return sum(np.sin(f * t) for f in FREQS[:n_sines])[:, None]

"""Input signals, one module per signal, found by the name a configuration
gives (``"signal": {"name": ...}``).  Each module has
``generate(rng, length, **params) -> (length, D) float64``."""
from __future__ import annotations

import importlib


def generate(spec: dict, rng, length: int):
    params = {k: v for k, v in spec.items() if k != "name"}
    mod = importlib.import_module(f"{__name__}.{spec['name']}")
    return mod.generate(rng, length, **params)

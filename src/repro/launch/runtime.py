"""Process set-up shared by the entry points that run on a device.

``chip_smoke.py``, ``launch/serve.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` before their first compile; tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: The in-checkout compile cache, used when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset.  A fixed path: the directory is part of what a later run looks up.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory and nothing
    else is set here.  Otherwise the cache lives at :data:`CACHE_DIR`.
    Every executable is cached: most of the engine's compile in under the
    one second JAX waits for by default, and together they are most of a
    cold start."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path

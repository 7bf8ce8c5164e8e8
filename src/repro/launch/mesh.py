"""Production mesh construction.

A FUNCTION (not a module constant) so importing this module never touches jax
device state — the dry-run sets the 512-placeholder-device XLA flag before any
jax initialization, and smoke tests/benches must keep seeing 1 device.

Every mesh axis is Auto-typed: the sharding rules place arrays with
``NamedSharding`` and let XLA propagate the rest.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(n_data: int = 1, n_model: int = 1):
    """Small host-device mesh: the serving arena's ``--mesh DxM`` and the
    sharded-arena parity checks."""
    return _auto_mesh((n_data, n_model), ("data", "model"))

"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (dry-runs set XLA_FLAGS before first jax init; smoke tests
see 1 device).
"""
from __future__ import annotations

import jax


def make_mesh_compat(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def mesh_devices(*, multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256

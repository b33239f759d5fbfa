"""Production mesh construction.

A function, NOT a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax call).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the launch steps place arrays with with_sharding_constraint,
    # which Explicit axes (jax.make_mesh's default since 0.9) reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (CPU smoke runs): (n, 1) mesh."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))

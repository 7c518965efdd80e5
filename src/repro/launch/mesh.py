"""Production mesh builders.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any device
query; smoke tests must keep seeing 1 device).
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis carries
    cross-pod data parallelism over DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic re-mesh)."""
    return _mesh(shape, axes)

"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device; only
dryrun.py forces 512 host devices — before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16×16 = 256 chips (data, model).
    Multi-pod: 2 pods × 256 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4,
                    multi_pod: bool = False) -> Mesh:
    """Small mesh for in-CI dry-run smoke tests (8 host devices)."""
    if multi_pod:
        return _make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _make_mesh((n_data, n_model), ("data", "model"))

"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax init; smoke tests see
1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_debug_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model places arrays
    with ``with_sharding_constraint`` and lets GSPMD propagate, which
    ``Explicit`` axes (the default of ``jax.make_mesh``) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod'
    axis (DCN-connected).  Axis meanings: 'pod' + 'data' carry FSDP/DP,
    'model' carries TP/EP."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU integration tests (uses however many host
    devices XLA_FLAGS provided)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))

"""Production mesh builders (function, not module-level constant — importing
this module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n):
    """All-Auto axis types: callers of these meshes shard with ``jit``
    shardings, not with Explicit-axis types."""
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod (TPU v5e); multi_pod adds the 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto(len(axes)))


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes), **_auto(len(axes)))


def make_local_mesh(model: int = 1):
    """Single-device mesh with the production axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"), **_auto(2))

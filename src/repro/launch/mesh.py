"""Production mesh construction.

A function — not a module-level constant — so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    # Auto axes: the sharding rules constrain with named PartitionSpecs,
    # which jax.make_mesh's default Explicit axes refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2, pipe: int | None = None):
    """Small mesh over host devices for integration tests."""
    if pipe:
        return _make_mesh((data, model, pipe), ("data", "model", "pipe"))
    return _make_mesh((data, model), ("data", "model"))

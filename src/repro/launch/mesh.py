"""Mesh construction.  Functions, not module-level constants — importing
this module never touches jax device state (the dry-run sets
XLA_FLAGS *before* any jax init)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_local_mesh"]


def _mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 (256-chip v5e pod); 2x16x16 (2 pods = 512 chips) multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over the actually-present devices (tests / examples)."""
    n = len(jax.devices())
    assert data * model <= n, f"mesh {data}x{model} > {n} devices"
    return _mesh((data, model), ("data", "model"))

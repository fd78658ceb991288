"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax init).

  single-pod:  (16, 16)      -> ("data", "model")       256 chips
  multi-pod:   (2, 16, 16)   -> ("pod", "data", "model") 512 chips
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """`jax.make_mesh` with every axis in Auto (GSPMD) mode."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU smoke/examples (same axis names)."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))

"""Public jit'd wrappers for the Pallas kernels.

On a TPU backend the kernels are compiled by Mosaic; a kernel that the
compiler refuses is an error, never a silent hand-off to the oracle. On
any other backend they run in the Pallas interpreter, which executes the
same kernel body and is what the CPU tests exercise. The pure-jnp oracles
in ref.py define the semantics either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .evict_argmin import evict_argmin_pallas
from .layout import to_tiles
from .interval_occupancy import (interval_occupancy_pallas,
                                 occupancy_feasible_pallas)

__all__ = ["EVICT_BLOCK_N", "evict_argmin", "interval_occupancy",
           "occupancy_feasible", "on_tpu"]

# victim selection's default block: 256 rows of 128 was the fastest of
# 2048..131072 elements for the 96-cell grid's batched call over 2^20
# objects on a TPU v5e (PERF.md); smaller tables run as one block
EVICT_BLOCK_N = 32768


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def evict_argmin(scores: jax.Array, touch: jax.Array, mask: jax.Array, *,
                 block_n: int = EVICT_BLOCK_N, use_pallas: bool | None = None):
    """Victim selection: lexicographic argmin of (score, touch) where mask.

    Takes flat (N,) tables, which the kernel path lays out as (rows, 128)
    tiles first, or tables already so tiled (`layout.to_tiles` with the same
    `block_n`), which it reads as they are. Returns the flat victim index.
    """
    if use_pallas is None:
        use_pallas = True
    if not use_pallas:
        return ref.evict_argmin_ref(scores.ravel(), touch.ravel(),
                                    mask.ravel())
    if scores.ndim == 1:
        scores, _ = to_tiles(scores, block_n, 0.0)
        touch, _ = to_tiles(touch.astype(jnp.int32), block_n,
                            jnp.iinfo(jnp.int32).max)
        mask, _ = to_tiles(mask.astype(jnp.int32), block_n, 0)
    return evict_argmin_pallas(scores, touch, mask, block_n=block_n,
                               interpret=not on_tpu())


def interval_occupancy(deltas: jax.Array, *, block_t: int = 2048,
                       use_pallas: bool | None = None) -> jax.Array:
    """Occupancy profile (inclusive prefix sum) of eq. (2)'s LHS."""
    if use_pallas is None:
        use_pallas = True
    if use_pallas:
        return interval_occupancy_pallas(deltas, block_t=block_t,
                                         interpret=not on_tpu())
    return ref.interval_occupancy_ref(deltas)


def occupancy_feasible(deltas: jax.Array, zcap: jax.Array, *,
                       block_t: int = 2048, use_pallas: bool | None = None):
    """Schedule feasibility: (occupancy profile, max excess over zcap).

    The device-resident check of cost-FOO's rounded schedule
    (DESIGN.md §4): deltas are the accepted intervals' range-adds, the
    fused scan carries occupancy + running max(occ - zcap) in VMEM.
    """
    if use_pallas is None:
        use_pallas = True
    if use_pallas:
        return occupancy_feasible_pallas(deltas, zcap, block_t=block_t,
                                         interpret=not on_tpu())
    return ref.occupancy_feasible_ref(deltas, zcap)

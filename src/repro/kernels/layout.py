"""Flat tables as (rows, 128) tiles: the layout the TPU's vector unit and
Mosaic's block rules want (lanes last, rows in multiples of 8 sublanes)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["LANES", "rows_per_step", "to_tiles"]

LANES = 128
SUBLANES = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def rows_per_step(rows: int, block: int) -> int:
    """Rows one grid step covers: about `block` elements, never fewer than
    8 rows and never more than the table's `rows`."""
    return min(rows, _round_up(max(1, block // LANES), SUBLANES))


def to_tiles(x: jax.Array, block: int, fill) -> tuple[jax.Array, int]:
    """Pad a flat (n,) array with `fill` and reshape it to (rows, 128).

    Returns (tiles, block_rows): element i sits at (i // 128, i % 128);
    `rows` is a multiple of `rows_per_step(rows, block)`.
    """
    n = x.shape[0]
    rows = _round_up(max(1, -(-n // LANES)), SUBLANES)
    step = rows_per_step(rows, block)
    rows = _round_up(rows, step)
    if rows * LANES != n:
        x = jnp.pad(x, (0, rows * LANES - n), constant_values=fill)
    return x.reshape(rows, LANES), step

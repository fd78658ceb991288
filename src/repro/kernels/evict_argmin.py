"""Pallas TPU kernel: masked lexicographic argmin — the eviction decision.

Every priority policy's inner loop (LRU/LFU/GDS/GDSF/Belady/cost-Belady,
paper §2) is "find the cached object with the smallest (score, last_touch)".
A heap does not vectorize; on TPU the whole object table streams through
VMEM and the reduction runs at vector width (DESIGN.md §3). The table is
laid out as (rows, 128) tiles; each sequential grid step reduces one block
of rows to its lexicographic (score, touch, index) minimum with three
masked `min` reductions over an index iota, and folds it into a running
minimum kept in the resident output blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import LANES, rows_per_step

__all__ = ["evict_argmin_pallas"]

_BIG = 3.4e38
_INT_BIG = 2**31 - 1


def _min11(x):
    """Minimum of a 2-D tile as a (1, 1) tile (no scalar extraction)."""
    return jnp.min(jnp.min(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _kernel(scores_ref, touch_ref, mask_ref, idx_out, val_out, touch_best,
            *, block_rows: int):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        val_out[...] = jnp.full(val_out.shape, _BIG, jnp.float32)
        touch_best[...] = jnp.full(touch_best.shape, _INT_BIG, jnp.int32)
        idx_out[...] = jnp.full(idx_out.shape, _INT_BIG, jnp.int32)

    s = jnp.where(mask_ref[...] != 0, scores_ref[...], jnp.float32(_BIG))
    shape = s.shape
    idx = (g * (block_rows * LANES)
           + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    m = _min11(s)
    tie = s <= m
    t = jnp.where(tie, touch_ref[...], _INT_BIG)
    mt = _min11(t)
    mi = _min11(jnp.where(tie & (t == mt), idx, _INT_BIG))

    bs, bt, bi = val_out[...], touch_best[...], idx_out[...]
    better = (m < bs) | ((m == bs) & ((mt < bt) | ((mt == bt) & (mi < bi))))
    val_out[...] = jnp.where(better, m, bs)
    touch_best[...] = jnp.where(better, mt, bt)
    idx_out[...] = jnp.where(better, mi, bi)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def evict_argmin_pallas(scores: jax.Array, touch: jax.Array, mask: jax.Array,
                        block_n: int = 32768, interpret: bool = False):
    """Lexicographic argmin of (score, touch, index) over mask != 0 entries.

    scores: (rows, 128) float; touch: (rows, 128) int32; mask: (rows, 128)
    int32 0/1 flags. Entry i of the table sits at (i // 128, i % 128), and
    `rows` is a multiple of `rows_per_step(rows, block_n)`, as
    `layout.to_tiles` lays a flat table out. Returns (victim_index, the
    entry's flat index, int32 scalar; victim_score float32 scalar); score
    is +BIG when the mask is empty.
    """
    rows = scores.shape[0]
    block_rows = rows_per_step(rows, block_n)
    if scores.shape[1:] != (LANES,) or rows % block_rows:
        raise ValueError(f"expected (rows, {LANES}) tiles with rows a "
                         f"multiple of {block_rows}, got {scores.shape}")
    s = scores.astype(jnp.float32)
    t = touch.astype(jnp.int32)
    k = mask.astype(jnp.int32)
    block = pl.BlockSpec((block_rows, LANES), lambda g: (g, 0))
    carry = pl.BlockSpec((1, LANES), lambda g: (0, 0))
    idx, val = pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows),
        grid=(s.shape[0] // block_rows,),
        in_specs=[block, block, block],
        out_specs=[carry, carry],
        out_shape=[jax.ShapeDtypeStruct((1, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((1, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.int32)],
        interpret=interpret,
    )(s, t, k)
    return idx[0, 0], val[0, 0]

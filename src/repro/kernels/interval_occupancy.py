"""Pallas TPU kernels: blocked occupancy scan + feasibility (eq. 2 LHS).

Feasibility checking / contention profiling of a retention schedule needs
the occupancy profile occ(p) = sum of sizes of intervals covering serving
instant p. With per-position deltas (+s_i at interval start, -s_i one past
its end) this is a prefix sum over the request timeline — on TPU a
sequential-grid blocked scan over (rows, 128) tiles: each grid step scans
its block with log-step shifted adds (`pltpu.roll`, lanes then rows) and
adds the running total, carried across steps as a broadcast VMEM vector.

`occupancy_feasible_pallas` fuses the feasibility verdict into the same
scan: the deltas ARE the range-adds of the rounding pass's accepted
intervals, and the kernel carries a running max of occ - zcap alongside
the prefix-sum carry, so "does the schedule ever exceed the cap" is one
device-resident pass instead of a host round-trip per interval
(DESIGN.md §4; dispatched behind `use_pallas` like `evict_argmin`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import LANES, to_tiles

__all__ = ["interval_occupancy_pallas", "occupancy_feasible_pallas"]

_NEG_BIG = -3.4e38


def _sum11(x):
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _max11(x):
    return jnp.max(jnp.max(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _scan_tile(x):
    """Inclusive row-major prefix sum of an (R, 128) float32 tile."""
    rows, lanes = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    k = 1
    while k < lanes:                       # prefix within each row
        x = x + jnp.where(lane >= k, pltpu.roll(x, k, 1), 0.0)
        k *= 2
    # each row's total, broadcast along its lanes (masked sum, no extract)
    tot = jnp.broadcast_to(
        jnp.sum(jnp.where(lane == lanes - 1, x, 0.0), axis=1, keepdims=True),
        x.shape)
    before = jnp.where(row >= 1, pltpu.roll(tot, 1, 0), 0.0)
    k = 1
    while k < rows:                        # exclusive prefix of row totals
        before = before + jnp.where(row >= k, pltpu.roll(before, k, 0), 0.0)
        k *= 2
    return x + before


def _scan_block(deltas_ref, carry_ref):
    """Occupancy of this block; advances the carried running total."""
    occ = _scan_tile(deltas_ref[...]) + carry_ref[...]
    rows, lanes = occ.shape
    last = ((jax.lax.broadcasted_iota(jnp.int32, occ.shape, 0) == rows - 1)
            & (jax.lax.broadcasted_iota(jnp.int32, occ.shape, 1) == lanes - 1))
    carry_ref[...] = jnp.broadcast_to(
        _sum11(jnp.where(last, occ, 0.0)), carry_ref.shape)
    return occ


def _kernel(deltas_ref, out_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.zeros(carry_ref.shape, jnp.float32)

    out_ref[...] = _scan_block(deltas_ref, carry_ref)


def _tiled(deltas, block_t):
    d, block_rows = to_tiles(deltas.astype(jnp.float32), block_t, 0.0)
    return d, block_rows, pl.BlockSpec((block_rows, LANES), lambda g: (g, 0))


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def interval_occupancy_pallas(deltas: jax.Array, block_t: int = 2048,
                              interpret: bool = False) -> jax.Array:
    """Inclusive prefix sum of (T,) float deltas -> (T,) float32 occupancy."""
    T = deltas.shape[0]
    d, block_rows, block = _tiled(deltas, block_t)
    out = pl.pallas_call(
        _kernel,
        grid=(d.shape[0] // block_rows,),
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(d.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.float32)],
        interpret=interpret,
    )(d)
    return out.reshape(-1)[:T]


def _feas_kernel(deltas_ref, zcap_ref, occ_ref, excess_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.zeros(carry_ref.shape, jnp.float32)
        excess_ref[...] = jnp.full(excess_ref.shape, _NEG_BIG, jnp.float32)

    occ = _scan_block(deltas_ref, carry_ref)
    occ_ref[...] = occ
    excess_ref[...] = jnp.maximum(excess_ref[...],
                                  _max11(occ - zcap_ref[...]))


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def occupancy_feasible_pallas(deltas: jax.Array, zcap: jax.Array,
                              block_t: int = 2048,
                              interpret: bool = False):
    """Blocked range-add scan + running-max feasibility in one pass.

    deltas: (T,) schedule range-adds in delta form; zcap: (T,) per-instant
    caps. Returns (occupancy (T,) float32, max excess occ - zcap, a float32
    scalar — feasible iff <= tolerance). Padding positions carry zcap =
    +big so they never win the max.
    """
    T = deltas.shape[0]
    d, block_rows, block = _tiled(deltas, block_t)
    z, _ = to_tiles(zcap.astype(jnp.float32), block_t, -_NEG_BIG)
    occ, excess = pl.pallas_call(
        _feas_kernel,
        grid=(d.shape[0] // block_rows,),
        in_specs=[block, block],
        out_specs=[block, pl.BlockSpec((1, LANES), lambda g: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(d.shape, jnp.float32),
                   jax.ShapeDtypeStruct((1, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.float32)],
        interpret=interpret,
    )(d, z)
    return occ.reshape(-1)[:T], excess[0, 0]

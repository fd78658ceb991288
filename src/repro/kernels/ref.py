"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are verified against
(tests/test_kernels.py sweeps shapes/dtypes and asserts allclose).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["evict_argmin_ref", "interval_occupancy_ref",
           "occupancy_feasible_ref"]


def evict_argmin_ref(scores: jax.Array, touch: jax.Array,
                     mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Lexicographic argmin of (score, touch) over masked entries.

    Returns (victim_index int32, victim_score). If nothing is cached the
    score is +big and index 0. This is the eviction decision of every
    priority policy (paper §2 "Policies"; DESIGN.md §3).
    """
    big = jnp.asarray(3.4e38, scores.dtype)
    s = jnp.where(mask, scores, big)
    min_s = jnp.min(s)
    tie = s <= min_s
    int_big = jnp.asarray(2**31 - 1, touch.dtype)
    victim = jnp.argmin(jnp.where(tie, touch, int_big)).astype(jnp.int32)
    return victim, s[victim]


def interval_occupancy_ref(deltas: jax.Array) -> jax.Array:
    """Inclusive prefix sum of per-position occupancy deltas.

    deltas[p] = sum of +s_i at interval starts / -s_i just past interval
    ends; the prefix sum is the LHS occupancy profile of eq. (2).
    """
    return jnp.cumsum(deltas, axis=0)


def occupancy_feasible_ref(deltas: jax.Array,
                           zcap: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Occupancy profile + worst excess over the per-instant cap.

    Returns (occ float32, max over tau of occ[tau] - zcap[tau]); the
    schedule is feasible iff the excess is <= tolerance. Semantics of the
    fused Pallas scan in interval_occupancy.py.
    """
    occ = jnp.cumsum(deltas.astype(jnp.float32), axis=0)
    return occ, jnp.max(occ - zcap.astype(jnp.float32))

"""Pallas kernels vs their jnp oracles — correctness at benchmark scale.

On a TPU backend the kernels run compiled by Mosaic; elsewhere they run in
the Pallas interpreter, whose times say nothing about the chip."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from .common import emit, timed


def main():
    rng = np.random.default_rng(0)
    scores = jnp.asarray(rng.standard_normal(65536).astype(np.float32))
    touch = jnp.asarray(rng.integers(0, 1 << 20, 65536).astype(np.int32))
    mask = jnp.asarray(rng.random(65536) < 0.7)
    (gi, gv), dt_e = timed(
        lambda: ops.evict_argmin(scores, touch, mask, block_n=8192), repeats=1)
    wi, wv = ref.evict_argmin_ref(scores, touch, mask)
    emit("kernel_evict_argmin_64k", dt_e,
         f"match={int(gi)==int(wi)};victim={int(gi)}")

    deltas = jnp.asarray(rng.integers(-3, 4, 100_000).astype(np.float32))
    occ_k, dt_o = timed(
        lambda: np.asarray(ops.interval_occupancy(deltas, block_t=8192)),
        repeats=1)
    occ_r = np.cumsum(np.asarray(deltas))
    emit("kernel_interval_occupancy_100k", dt_o,
         f"allclose={bool(np.allclose(occ_k, occ_r, rtol=1e-5, atol=1e-3))}")

    # occupancy + worst excess over zcap in one pass (cost_foo validate=True)
    zcap = jnp.asarray(rng.integers(0, 6, 100_000).astype(np.float32))
    (occ_f, ex_f), dt_f = timed(
        lambda: ops.occupancy_feasible(deltas, zcap, block_t=8192), repeats=1)
    occ_w, ex_w = ref.occupancy_feasible_ref(deltas, zcap)
    ok = (np.allclose(np.asarray(occ_f), np.asarray(occ_w), rtol=1e-5,
                      atol=1e-3)
          and abs(float(ex_f) - float(ex_w)) < 1e-3)
    emit("kernel_occupancy_feasible_100k", dt_f,
         f"match={ok};excess={float(ex_f):.1f}")
    return None


if __name__ == "__main__":
    main()
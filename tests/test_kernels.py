"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles.

Property-based (hypothesis) variants live in test_kernels_property.py so this
module collects even where hypothesis is not installed.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.evict_argmin import evict_argmin_pallas


@pytest.mark.parametrize("N,block_n,dtype", [
    (128, 64, jnp.float32), (1000, 256, jnp.float32),
    (8192, 2048, jnp.float32), (555, 128, jnp.bfloat16),
    (2048, 512, jnp.bfloat16),
])
def test_evict_argmin_shapes(N, block_n, dtype):
    rng = np.random.default_rng(N)
    scores = rng.standard_normal(N).astype(np.float32)
    touch = rng.integers(0, 10_000, N).astype(np.int32)
    mask = rng.random(N) < 0.5
    if not mask.any():
        mask[0] = True
    s = jnp.asarray(scores).astype(dtype)
    gi, gv = ops.evict_argmin(s, jnp.asarray(touch), jnp.asarray(mask),
                              block_n=block_n)
    wi, wv = ref.evict_argmin_ref(s, jnp.asarray(touch), jnp.asarray(mask))
    assert int(gi) == int(wi)
    np.testing.assert_allclose(np.float32(gv), np.float32(wv), rtol=1e-6)


def test_evict_argmin_lexicographic_ties():
    scores = jnp.zeros(512, jnp.float32)  # all tied
    touch = jnp.arange(512, 0, -1, dtype=jnp.int32)  # last entry oldest
    mask = jnp.ones(512, bool)
    gi, _ = ops.evict_argmin(scores, touch, mask, block_n=128)
    assert int(gi) == 511  # smallest touch wins


def test_evict_argmin_empty_mask():
    scores = jnp.zeros(128, jnp.float32)
    touch = jnp.zeros(128, jnp.int32)
    mask = jnp.zeros(128, bool)
    _, gv = ops.evict_argmin(scores, touch, mask, block_n=64)
    assert float(gv) > 1e37  # +BIG sentinel


@pytest.mark.parametrize("case", ["random", "touch_ties", "index_ties",
                                  "empty"])
def test_evict_argmin_tiled_entry(case):
    """The kernel on (rows, 128) tiles, read as they are (three grid steps
    of 8 rows), against the oracle on the flattened tables: ties in score
    go to the oldest touch, then to the lowest index."""
    rng = np.random.default_rng(len(case))
    rows = 24
    scores = rng.integers(0, 4, (rows, 128)).astype(np.float32)
    touch = rng.integers(0, 50, (rows, 128)).astype(np.int32)
    mask = (rng.random((rows, 128)) < 0.3).astype(np.int32)
    if case == "touch_ties":
        scores[:] = 1.0
    elif case == "index_ties":
        scores[:] = 1.0
        touch[:] = 7
    elif case == "empty":
        mask[:] = 0
    gi, gv = evict_argmin_pallas(jnp.asarray(scores), jnp.asarray(touch),
                                 jnp.asarray(mask), block_n=1024,
                                 interpret=True)
    wi, wv = ref.evict_argmin_ref(jnp.asarray(scores.ravel()),
                                  jnp.asarray(touch.ravel()),
                                  jnp.asarray(mask.ravel() != 0))
    assert (int(gi), float(gv)) == (int(wi), float(wv))
    flat = mask.ravel() != 0
    if case == "empty":
        assert float(gv) > 1e37  # +BIG sentinel
    if case == "index_ties":
        assert int(gi) == int(np.flatnonzero(flat)[0])
    if case == "touch_ties":
        assert touch.ravel()[int(gi)] == touch.ravel()[flat].min()


def test_evict_argmin_tiled_entry_refuses_partial_blocks():
    tiles = jnp.zeros((12, 128), jnp.float32)
    with pytest.raises(ValueError):
        evict_argmin_pallas(tiles, tiles.astype(jnp.int32),
                            tiles.astype(jnp.int32), block_n=1024,
                            interpret=True)


@pytest.mark.parametrize("T,block_t,dtype", [
    (100, 32, jnp.float32), (4096, 1024, jnp.float32),
    (777, 256, jnp.float32), (2000, 512, jnp.int32),
])
def test_interval_occupancy_shapes(T, block_t, dtype):
    rng = np.random.default_rng(T)
    deltas = rng.integers(-3, 4, T).astype(np.float32)
    got = np.asarray(ops.interval_occupancy(
        jnp.asarray(deltas).astype(dtype), block_t=block_t))
    want = np.cumsum(deltas.astype(np.float32))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("T,block_t,dtype", [
    (100, 32, jnp.float32), (4096, 1024, jnp.float32),
    (777, 256, jnp.float32), (2000, 512, jnp.int32), (1, 8, jnp.float32),
    (2049, 2048, jnp.float32),
])
def test_occupancy_feasible_shapes(T, block_t, dtype):
    rng = np.random.default_rng(T * 7 + 1)
    deltas = rng.integers(-3, 4, T).astype(np.float32)
    zcap = rng.integers(0, 8, T).astype(np.float32)
    got_occ, got_ex = ops.occupancy_feasible(
        jnp.asarray(deltas).astype(dtype), jnp.asarray(zcap),
        block_t=block_t)
    want_occ, want_ex = ref.occupancy_feasible_ref(
        jnp.asarray(deltas).astype(dtype), jnp.asarray(zcap))
    np.testing.assert_allclose(np.asarray(got_occ), np.asarray(want_occ),
                               rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(float(got_ex), float(want_ex),
                               rtol=1e-6, atol=1e-5)


def test_occupancy_feasible_sign():
    """excess <= 0 iff the schedule fits under zcap at every instant."""
    deltas = jnp.asarray(np.array([2.0, 1.0, -1.0, 3.0], np.float32))
    zcap_ok = jnp.asarray(np.array([5.0, 5.0, 5.0, 5.0], np.float32))
    zcap_bad = jnp.asarray(np.array([5.0, 5.0, 5.0, 4.0], np.float32))
    _, ex_ok = ops.occupancy_feasible(deltas, zcap_ok, block_t=2)
    _, ex_bad = ops.occupancy_feasible(deltas, zcap_bad, block_t=2)
    assert float(ex_ok) <= 0.0       # occ = [2,3,2,5] fits under 5
    assert float(ex_bad) == 1.0      # final instant: 5 vs cap 4


def test_occupancy_of_opt_schedule_respects_budget():
    """End-to-end: the exact optimum's schedule through the kernel is
    feasible at every serving instant."""
    from repro.core import exact_opt_uniform
    rng = np.random.default_rng(7)
    T, N, B = 2000, 100, 12
    ids = rng.integers(0, N, T).astype(np.int32)
    costs = rng.lognormal(0, 2, N)
    r = exact_opt_uniform(ids, costs, B, return_selected=True)
    deltas = np.zeros(T, np.float32)
    for iv in r.selected:
        deltas[iv.t + 1] += 1
        if iv.u < T:
            deltas[iv.u] -= 1
    occ = np.asarray(ops.interval_occupancy(jnp.asarray(deltas)))
    assert occ.max() <= B - 1 + 1e-6
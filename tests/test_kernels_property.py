"""Hypothesis property tests for the Pallas kernels.

Guarded with `pytest.importorskip`: hypothesis is optional in the container,
and collection must not die where it is absent (the fixed-seed sweeps in
test_kernels.py cover the same oracles either way).
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops, ref  # noqa: E402


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_evict_argmin_ties_property(data):
    """Few distinct scores and touches force ties on both keys: the
    blocked kernel must still pick the oracle's (score, touch, index)
    minimum, whichever block it sits in."""
    n = data.draw(st.integers(1, 3000))
    block = data.draw(st.sampled_from([128, 1024, 2048]))
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    scores = jnp.asarray(rng.integers(0, 3, n).astype(np.float32))
    touch = jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < data.draw(st.sampled_from([0.01, 0.5])))
    gi, gv = ops.evict_argmin(scores, touch, mask, block_n=block)
    wi, wv = ref.evict_argmin_ref(scores, touch, mask)
    assert (int(gi), float(gv)) == (int(wi), float(wv))


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_occupancy_feasible_property(data):
    """Integer deltas keep every partial sum exact in float32, so the
    log-step tile scan must equal the sequential cumsum bit for bit."""
    T = data.draw(st.integers(1, 5000))
    block = data.draw(st.sampled_from([128, 1024, 2048]))
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    deltas = rng.integers(-50, 51, T).astype(np.float32)
    zcap = rng.integers(0, 200, T).astype(np.float32)
    occ, ex = ops.occupancy_feasible(jnp.asarray(deltas), jnp.asarray(zcap),
                                     block_t=block)
    want = np.cumsum(deltas)
    np.testing.assert_array_equal(np.asarray(occ), want)
    assert float(ex) == float((want - zcap).max())

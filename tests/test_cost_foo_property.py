"""Hypothesis property tests for cost-FOO's segment-tree rounding.

The fast `round_fractional` (lazy range-add/range-min headroom tree,
DESIGN.md §4) must be *bit-identical* to `round_fractional_reference`
(the pre-optimization quadratic oracle): same greedy ordering keys, same
float expression shapes, same stable sort — so the accepted set, the
saved-dollar accumulation order, and hence the final float agree exactly.
Sizes are drawn integer-valued so all occupancy arithmetic is exact and
the relative tolerance can never flip a comparison between the two paths.

Guarded with `pytest.importorskip`: hypothesis is optional in the
container; the fixed-seed parity checks in test_cost_foo.py cover the
same claim where it is absent.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (Trace, build_interval_arrays,  # noqa: E402
                        interval_deltas, round_fractional,
                        round_fractional_reference, zcap_profile)
from repro.core.cost_foo import _round_arrays, _round_tol  # noqa: E402
from repro.core.opt_exact import lp_opt  # noqa: E402


def _draw_instance(data):
    T = data.draw(st.integers(4, 60))
    N = data.draw(st.integers(2, 8))
    ids = np.array(data.draw(st.lists(st.integers(0, N - 1),
                                      min_size=T, max_size=T)), np.int32)
    # integer sizes keep occupancy arithmetic exact (see module docstring)
    sizes = np.array(data.draw(st.lists(st.integers(1, 9),
                                        min_size=N, max_size=N)), np.float64)
    B = float(data.draw(st.integers(1, 30)))
    return ids, sizes, B


@settings(max_examples=80, deadline=None)
@given(st.data())
def test_segment_tree_rounding_bit_identical(data):
    """Hypothesis: fast rounding == quadratic reference, bit for bit."""
    ids, sizes, B = _draw_instance(data)
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    # varied miss costs make the density tiebreak order nontrivial
    costs = rng.lognormal(0.0, 1.0, len(sizes))
    t, u, obj, save, size = build_interval_arrays(ids, costs, sizes)
    if len(t) == 0:
        return
    # arbitrary fractional x in [0, 1] — rounding must agree on ANY x,
    # not just LP solutions
    x = rng.random(len(t))
    from repro.core.opt_exact import Interval
    paid_iv = [Interval(int(tt), int(uu), int(oo), float(sv), float(sz))
               for tt, uu, oo, sv, sz in zip(t, u, obj, save, size)]
    fast = round_fractional(ids, sizes, B, x, paid_iv)
    ref = round_fractional_reference(ids, sizes, B, x, paid_iv)
    assert fast == ref  # exact float equality, not approx


@settings(max_examples=80, deadline=None)
@given(st.data())
def test_rounded_schedule_never_exceeds_zcap(data):
    """Hypothesis: the accepted set's occupancy respects zcap everywhere."""
    ids, sizes, B = _draw_instance(data)
    seed = data.draw(st.integers(0, 2**31 - 1))
    t, u, obj, save, size = build_interval_arrays(
        ids, np.ones_like(sizes), sizes)
    if len(t) == 0:
        return
    rng = np.random.default_rng(seed)
    x = rng.random(len(t))
    T = len(ids)
    zcap = zcap_profile(ids, sizes, B)
    tol = _round_tol(B)
    _, accepted = _round_arrays(t, u, save, size, x, zcap, tol)
    if not len(accepted):
        return
    deltas = interval_deltas(t[accepted], u[accepted], size[accepted], T)
    occ = np.cumsum(deltas)
    assert (occ[1:] <= zcap[1:] + tol).all(), (
        float((occ[1:] - zcap[1:]).max()), tol)


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_rounding_lp_solution_bounded_by_lp(data):
    """Hypothesis: rounding the LP's own x never beats the LP bound."""
    ids, sizes, B = _draw_instance(data)
    costs = np.ones_like(sizes)
    _, lp_savings, x, paid = lp_opt(ids, costs, sizes, B)
    if not paid:
        return
    saved = round_fractional(ids, sizes, B, x, paid)
    assert saved <= lp_savings + 1e-9 * max(1.0, lp_savings)

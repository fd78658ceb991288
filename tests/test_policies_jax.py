"""JAX lax.scan policy simulator == Python reference, step for step."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Trace, simulate
from repro.core.policies_jax import (POLICY_WEIGHTS, _simulate, simulate_jax,
                                     stack_policy_weights, sweep_jax)
from repro.core.trace import next_use_indices


def _rand(rng, T, N):
    ids = rng.integers(0, N, T).astype(np.int32)
    # power-of-two costs: every score the policies form is exact in f32,
    # so the JAX sim must match the f64 Python reference bit-for-bit
    costs = 2.0 ** rng.integers(0, 12, N)
    return ids, costs


@pytest.mark.parametrize("policy", ["lru", "lfu", "gds", "gdsf",
                                    "belady", "cost_belady"])
def test_jax_matches_python_uniform(policy):
    rng = np.random.default_rng(hash(policy) % 2**32)
    for trial in range(8):
        T = int(rng.integers(50, 300))
        N = int(rng.integers(5, 40))
        B = int(rng.integers(1, max(2, N // 2)))
        ids, costs = _rand(rng, T, N)
        tr = Trace(ids=ids, sizes=np.ones(N))
        ref = simulate(policy, tr, costs, float(B))
        d, h = simulate_jax(policy, ids, costs, B, num_objects=N)
        assert h == ref.hits, f"{policy} trial={trial} hits {h} != {ref.hits}"
        assert d == pytest.approx(ref.dollars, rel=1e-5), f"{policy} t={trial}"


def test_sweep_shape_and_consistency():
    rng = np.random.default_rng(0)
    ids, costs = _rand(rng, 200, 20)
    cost_matrix = np.stack([costs, 10 * costs, costs ** 2])
    budgets = np.array([2, 4, 8])
    out = sweep_jax("gdsf", ids, cost_matrix, budgets, num_objects=20)
    assert out.shape == (3, 3)
    # more budget never costs more dollars (same price vector)
    assert (np.diff(out, axis=1) <= 1e-4).all()
    # single-cell agreement
    d, _ = simulate_jax("gdsf", ids, cost_matrix[1], 4, num_objects=20)
    assert out[1, 1] == pytest.approx(d, rel=1e-6)


def test_all_policies_registered():
    assert set(POLICY_WEIGHTS) == {"lru", "lfu", "gds", "gdsf",
                                   "belady", "cost_belady"}


def test_multi_policy_sweep_matches_per_cell():
    """The (policies x prices x budgets) grid — one compiled program —
    reproduces every per-cell simulate_jax result exactly."""
    rng = np.random.default_rng(7)
    ids, costs = _rand(rng, 250, 24)
    cost_matrix = np.stack([costs, 8 * costs, costs / 4, 64 * costs])
    budgets = np.array([2, 4, 8, 12])
    policies = list(POLICY_WEIGHTS)
    out = sweep_jax(policies, ids, cost_matrix, budgets, num_objects=24)
    assert out.shape == (6, 4, 4)
    for q, pol in enumerate(policies):
        for p in range(4):
            for k, B in enumerate(budgets):
                d, _ = simulate_jax(pol, ids, cost_matrix[p], int(B),
                                    num_objects=24)
                assert out[q, p, k] == np.float32(d), \
                    f"cell ({pol}, price {p}, B={B})"


def test_multi_policy_sweep_accepts_weight_stack():
    rng = np.random.default_rng(8)
    ids, costs = _rand(rng, 120, 10)
    stack = stack_policy_weights(["lru", "belady"])
    out = sweep_jax(stack, ids, costs[None, :], np.array([3]), num_objects=10)
    assert out.shape == (2, 1, 1)
    ref = sweep_jax(["lru", "belady"], ids, costs[None, :], np.array([3]),
                    num_objects=10)
    np.testing.assert_array_equal(out, ref)
    with pytest.raises(ValueError):
        sweep_jax(np.zeros((2, 5), np.float32), ids, costs[None, :],
                  np.array([3]), num_objects=10)


@pytest.mark.parametrize("policy", ["lru", "gdsf", "cost_belady"])
def test_pallas_victim_path_matches_jnp_step_for_step(policy):
    """`_simulate` with the Pallas evict_argmin kernel (interpret mode on
    CPU) must track the jnp victim path through the WHOLE trajectory, not
    just the final totals."""
    rng = np.random.default_rng(hash(policy) % 2**32)
    T, N, B = 150, 16, 5
    ids, costs = _rand(rng, T, N)
    nxt = next_use_indices(ids).astype(np.int32)
    args = (jnp.asarray(ids), jnp.asarray(nxt),
            jnp.asarray(costs, jnp.float32), jnp.ones(N, jnp.float32),
            jnp.int32(B), jnp.asarray(POLICY_WEIGHTS[policy].as_array()), N)
    d_j, h_j, (dol_j, hit_j) = _simulate(*args, use_pallas=False,
                                         trace_steps=True)
    d_p, h_p, (dol_p, hit_p) = _simulate(*args, use_pallas=True,
                                         trace_steps=True)
    np.testing.assert_array_equal(np.asarray(hit_j), np.asarray(hit_p))
    np.testing.assert_array_equal(np.asarray(dol_j), np.asarray(dol_p))
    assert float(d_j) == float(d_p) and int(h_j) == int(h_p)


def test_pallas_victim_path_full_api():
    """End-to-end through simulate_jax/sweep_jax with use_pallas=True."""
    rng = np.random.default_rng(9)
    ids, costs = _rand(rng, 100, 12)
    for policy in ("lfu", "gds", "belady"):
        d1, h1 = simulate_jax(policy, ids, costs, 4, num_objects=12,
                              use_pallas=False)
        d2, h2 = simulate_jax(policy, ids, costs, 4, num_objects=12,
                              use_pallas=True)
        assert (d1, h1) == (d2, h2), policy
    out_j = sweep_jax(["lru", "gdsf"], ids, costs[None, :], np.array([3, 6]),
                      num_objects=12, use_pallas=False)
    out_p = sweep_jax(["lru", "gdsf"], ids, costs[None, :], np.array([3, 6]),
                      num_objects=12, use_pallas=True)
    np.testing.assert_array_equal(out_j, out_p)


def test_step_scopes_cover_the_compiled_loop_cpu():
    """On the jnp path, every instruction of the compiled grid's entry and
    loop computations lands in one of the step's three scopes or in
    `unscoped` by the stated rule, and each scope holds device work."""
    import re

    import repro.core.policies_jax as pj
    from repro.launch.hlo_analysis import (_INSTRUCTION, _OP_NAME, _calls,
                                           _split_computations)
    from repro.obs import Tracer
    rng = np.random.default_rng(11)
    ids, costs = _rand(rng, 90, 30)
    tracer = Tracer()
    sweep_jax(list(POLICY_WEIGHTS), ids, np.stack([costs, 3 * costs]),
              np.array([3, 9]), num_objects=30, use_pallas=False,
              tracer=tracer)
    scopes = tracer.spans(name="replay.compile")[0].attrs["scopes"]
    assert pj.step_scopes() == scopes
    assert set(scopes) == {*pj.STEP_SCOPES, "unscoped"}
    where = {n: s for s, names in scopes.items() for n in names}
    assert len(where) == sum(map(len, scopes.values()))
    text = next(reversed(pj._EXECUTABLES.values())).as_text()
    comps = _split_computations(text)
    entry = re.search(r"ENTRY\s+%?([\w.\-]+)", text).group(1)
    loops = [c for c, kind in _calls(comps[entry])
             if kind in ("body", "condition")]
    assert loops
    for comp in [entry, *loops]:
        for ls in comps[comp]:
            m = _INSTRUCTION.match(ls)
            if not m:
                continue
            op = _OP_NAME.search(ls)
            named = [p for p in (op.group(1) if op else "").split("/")
                     if p in pj.STEP_SCOPES]
            if named:
                assert where[m.group(1)] == named[-1], ls
            elif op and op.group(1):
                assert where[m.group(1)] == "unscoped", ls
            else:
                assert m.group(1) in where, ls
    assert any("reduce" in n for n in scopes["replay.victim"])
    assert all(scopes[s] for s in pj.STEP_SCOPES)


def test_no_tracer_builds_no_scope_map_and_opens_no_span(monkeypatch):
    """No tracer, a NullTracer or a disabled Tracer: the first call of a
    shape compiles without building the scope map, and nothing is
    recorded."""
    import repro.core.policies_jax as pj
    from repro.obs import NullTracer, Tracer

    def refuse(hlo):
        raise AssertionError("scope map built without a tracer")
    monkeypatch.setattr(pj, "_scope_map", refuse)
    monkeypatch.setattr(pj, "_EXECUTABLES", {})
    rng = np.random.default_rng(12)
    ids, costs = _rand(rng, 60, 17)
    off = Tracer(enabled=False)
    for tracer in (None, NullTracer(), off):
        pj._EXECUTABLES.clear()
        out = sweep_jax("lru", ids, costs[None, :], np.array([2]),
                        num_objects=17, tracer=tracer)
        assert out.shape == (1, 1)
    assert off.spans() == []


def test_compiled_grids_are_bounded_and_reused(monkeypatch):
    import repro.core.policies_jax as pj
    monkeypatch.setattr(pj, "_EXECUTABLES", {})
    monkeypatch.setattr(pj, "_MAX_EXECUTABLES", 2)
    rng = np.random.default_rng(13)
    ids, costs = _rand(rng, 40, 9)
    for n in (9, 10, 11, 11):
        sweep_jax("lfu", ids, np.resize(costs, (1, n)), np.array([2]),
                  num_objects=n)
    assert [k[1] for k in pj._EXECUTABLES] == [10, 11]


@pytest.mark.parametrize("policy", list(POLICY_WEIGHTS))
@pytest.mark.parametrize("N", [1000, 3000])
def test_padded_tiles_kernel_jnp_and_python_agree(N, policy):
    """Tables whose length is no multiple of 1,024 leave padding in the
    (rows, 128) tiles of the scan's state. The kernel and the jnp victim
    paths track each other step for step and both match the Python
    reference, in a cell of budget 1, a mid-size one, and one whose cache
    never fills."""
    rng = np.random.default_rng(N)
    T = 400
    ids, costs = _rand(rng, T, N)
    # the upper half of the ids, so the padding sits next to live objects
    ids = (ids % (N // 2) + N // 2).astype(np.int32)
    tr = Trace(ids=ids, sizes=np.ones(N))
    nxt = next_use_indices(ids).astype(np.int32)
    distinct = len(np.unique(ids))
    w = jnp.asarray(POLICY_WEIGHTS[policy].as_array())
    for B in (1, distinct // 4, distinct + 1):
        args = (jnp.asarray(ids), jnp.asarray(nxt),
                jnp.asarray(costs, jnp.float32), jnp.ones(N, jnp.float32),
                jnp.int32(B), w, N)
        d_j, h_j, (dol_j, hit_j) = _simulate(*args, use_pallas=False,
                                             trace_steps=True)
        d_p, h_p, (dol_p, hit_p) = _simulate(*args, use_pallas=True,
                                             trace_steps=True)
        np.testing.assert_array_equal(np.asarray(hit_j), np.asarray(hit_p))
        np.testing.assert_array_equal(np.asarray(dol_j), np.asarray(dol_p))
        ref = simulate(policy, tr, costs, float(B))
        assert int(h_j) == ref.hits, f"{policy} B={B}"
        assert float(d_j) == pytest.approx(ref.dollars, rel=1e-5), \
            f"{policy} B={B}"
        if B > distinct:
            assert ref.hits == T - distinct  # never full: cold misses only

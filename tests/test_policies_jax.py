"""JAX lax.scan policy simulator == Python reference, step for step."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Trace, simulate
from repro.core.policies_jax import (POLICY_WEIGHTS, _simulate, cost_density,
                                     simulate_jax, stack_policy_weights,
                                     sweep_jax)
import repro.core.policies_jax as pj
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


def _byte_sizes(rng, N, B):
    """Power-of-two sizes under a budget of B bytes, with object 0 as
    large as the cache (it evicts everything) and object 1 larger (it is
    fetched through)."""
    sizes = 2 ** rng.integers(0, 4, N)
    sizes[:2] = B, B + 1
    return sizes


@pytest.mark.parametrize("unit", ["pages", "bytes"])
@pytest.mark.parametrize("policy", ["lru", "gdsf", "cost_belady"])
def test_pallas_victim_path_matches_jnp_step_for_step(policy, unit):
    """`_simulate` with the Pallas evict_argmin kernel (interpret mode on
    CPU) must track the jnp victim path through the WHOLE trajectory, not
    just the final totals. Under a byte budget the trajectory holds a step
    that evicts many victims and a fetch-through, and the counts of both
    follow it step for step too."""
    rng = np.random.default_rng(hash(policy) % 2**32)
    T, N, B = 150, 16, 5 if unit == "pages" else 24
    ids, costs = _rand(rng, T, N)
    nxt = next_use_indices(ids).astype(np.int32)
    sizes = np.ones(N) if unit == "pages" else _byte_sizes(rng, N, B)
    args = (jnp.asarray(ids), jnp.asarray(nxt),
            jnp.asarray(costs, jnp.float32), jnp.asarray(sizes, jnp.float32),
            jnp.asarray(sizes, jnp.int32),
            jnp.asarray(cost_density(costs, sizes)),
            jnp.int32(B), jnp.asarray(POLICY_WEIGHTS[policy].as_array()), N)
    *end_j, traj_j = _simulate(*args, use_pallas=False, trace_steps=True)
    *end_p, traj_p = _simulate(*args, use_pallas=True, trace_steps=True)
    for a, b in zip(traj_j, traj_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [float(x) for x in end_j] == [float(x) for x in end_p]
    if unit == "bytes":
        evictions, bypasses = (np.diff(np.asarray(x), prepend=0)
                               for x in traj_j[2:])
        assert evictions.max() >= 4 and bypasses.max() == 1
        ref = simulate(policy, Trace(ids=ids, sizes=sizes.astype(float)),
                       costs, float(B))
        assert int(end_j[2]) == ref.evictions
        assert int(end_j[3]) == int(np.sum(ids == 1))
        assert float(end_j[0]) == pytest.approx(ref.dollars, rel=1e-6)


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


@pytest.mark.parametrize("unit", ["pages", "bytes"])
def test_step_scopes_cover_the_compiled_loop_cpu(unit, monkeypatch):
    """On the jnp path, every instruction of the compiled grid's entry and
    loop computations (the eviction loop's too) lands in one of the step's
    scopes or in `unscoped` by the stated rule, and each scope holds
    device work, the victim selection's reductions in the loop's."""
    import re

    from repro.launch.hlo_analysis import (_INSTRUCTION, _OP_NAME, _calls,
                                           _split_computations)
    from repro.obs import Tracer
    monkeypatch.setattr(pj, "_EXECUTABLES", {})
    rng = np.random.default_rng(11)
    ids, costs = _rand(rng, 90, 30)
    tracer = Tracer()
    sweep_jax(list(POLICY_WEIGHTS), ids, np.stack([costs, 3 * costs]),
              np.array([3, 9]), num_objects=30, use_pallas=False,
              tracer=tracer, sizes=np.arange(30) % 4 + 1, budget_unit=unit)
    scopes = tracer.spans(name="replay.compile")[0].attrs["scopes"]
    assert pj.step_scopes() == scopes
    assert set(scopes) == {*pj.STEP_SCOPES, "unscoped"}
    where = {n: s for s, names in scopes.items() for n in names}
    assert len(where) == sum(map(len, scopes.values()))
    text = next(reversed(pj._EXECUTABLES.values())).as_text()
    comps = _split_computations(text)
    entry = re.search(r"ENTRY\s+%?([\w.\-]+)", text).group(1)
    loops, todo = [], [entry]
    while todo:
        found = [c for c, kind in _calls(comps[todo.pop()])
                 if kind in ("body", "condition")]
        loops += found
        todo += found
    assert len(loops) == 4
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
    assert any("reduce" in n for n in scopes["replay.evict_bytes"])
    assert all(scopes[s] for s in pj.STEP_SCOPES)


def test_no_tracer_builds_no_scope_map_and_opens_no_span(monkeypatch):
    """No tracer, a NullTracer or a disabled Tracer: the first call of a
    shape compiles without building the scope map, and nothing is
    recorded."""
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
                jnp.ones(N, jnp.int32),
                jnp.asarray(cost_density(costs, np.ones(N))),
                jnp.int32(B), w, N)
        d_j, h_j, *_, (dol_j, hit_j, *_) = _simulate(
            *args, use_pallas=False, trace_steps=True)
        d_p, h_p, *_, (dol_p, hit_p, *_) = _simulate(
            *args, use_pallas=True, trace_steps=True)
        np.testing.assert_array_equal(np.asarray(hit_j), np.asarray(hit_p))
        np.testing.assert_array_equal(np.asarray(dol_j), np.asarray(dol_p))
        ref = simulate(policy, tr, costs, float(B))
        assert int(h_j) == ref.hits, f"{policy} B={B}"
        assert float(d_j) == pytest.approx(ref.dollars, rel=1e-5), \
            f"{policy} B={B}"
        if B > distinct:
            assert ref.hits == T - distinct  # never full: cold misses only


_PRICES = [(4e-7, 9e-11), (4e-7, 2e-11), (4e-8, 1.2e-10), (4e-8, 8.7e-11)]


@pytest.mark.parametrize("use_pallas", [False, True])
def test_byte_budget_grid_matches_python(use_pallas):
    """The 96-cell grid under byte budgets, at the CDN law's shapes
    (`wiki_cdn_like`: Pareto sizes to tens of MB, a Zipf core of small
    objects, one-hit wonders), on the jnp path and on the kernel in
    interpret mode (two policies, for time), equals `policies.py` cell by
    cell: dollars, evictions and fetch-throughs; the trace span carries
    their sums."""
    from repro.core.trace import wiki_cdn_like
    from repro.obs import Tracer
    tr = wiki_cdn_like(2048, 512, seed=5)
    sizes = np.round(tr.sizes)
    cm = np.stack([f + sizes * e for f, e in _PRICES])
    budgets = np.round(sizes.sum() * np.array([1 / 128, 1 / 64, 1 / 32,
                                               1 / 16]))
    assert sizes.max() > budgets[0]
    policies = ["gdsf", "cost_belady"] if use_pallas else list(POLICY_WEIGHTS)
    tracer = Tracer()
    out = sweep_jax(policies, tr.ids, cm, budgets, num_objects=2048,
                    sizes=sizes, use_pallas=use_pallas, tracer=tracer,
                    budget_unit="bytes")
    args = (jnp.asarray(stack_policy_weights(policies)), jnp.asarray(tr.ids),
            jnp.asarray(next_use_indices(tr.ids).astype(np.int32)),
            jnp.asarray(cm, jnp.float32), jnp.asarray(cost_density(cm, sizes)),
            jnp.asarray(sizes, jnp.float32), jnp.asarray(sizes, jnp.int32),
            jnp.asarray(budgets, jnp.int32))
    d, evictions, bypasses, trips = (np.asarray(x) for x in pj._sweep_grid(
        *args, 2048, use_pallas))
    np.testing.assert_array_equal(d, out)
    for q, pol in enumerate(policies):
        for p in range(len(cm)):
            for k, B in enumerate(budgets):
                ref = simulate(pol, Trace(ids=tr.ids, sizes=sizes), cm[p],
                               float(B))
                assert out[q, p, k] == pytest.approx(ref.dollars, rel=1e-6)
                assert evictions[q, p, k] == ref.evictions
                assert bypasses[q, p, k] == np.sum(sizes[tr.ids] > B)
    attrs = tracer.spans(name="replay.execute")[0].attrs
    assert attrs == {"evict_trips": int(trips),
                     "evictions": int(evictions.sum()),
                     "bypasses": int(bypasses.sum())}
    # the loop runs as often as the cell that evicts most at each step
    assert evictions.max() <= trips <= evictions.sum()
    assert bypasses.sum() > 0


def test_page_budget_is_a_byte_budget_of_one_size():
    """A budget of pages replays as a byte budget in which every object
    takes the same bytes: the same dollars, evictions and loop trips, bit
    for bit, and a budget of 0 pages fetches every request through."""
    from repro.obs import Tracer
    rng = np.random.default_rng(14)
    ids, costs = _rand(rng, 200, 24)
    cm = np.stack([costs, 2 * costs])
    sizes = np.full(24, 1000)
    attrs = {}
    for unit, scale in (("pages", 1), ("bytes", 1000)):
        tracer = Tracer()
        out = sweep_jax(list(POLICY_WEIGHTS), ids, cm,
                        np.array([0, 3, 7]) * scale, num_objects=24,
                        sizes=sizes, use_pallas=False, tracer=tracer,
                        budget_unit=unit)
        attrs[unit] = tracer.spans(name="replay.execute")[0].attrs
        if unit == "pages":
            pages = out
    np.testing.assert_array_equal(pages, out)
    assert attrs["pages"] == attrs["bytes"]
    assert attrs["pages"]["bypasses"] == len(POLICY_WEIGHTS) * 2 * len(ids)
    np.testing.assert_allclose(
        pages[:, :, 0], np.broadcast_to(cm[:, ids].sum(1), pages.shape[:2]),
        rtol=1e-6)


def test_byte_budget_needs_whole_byte_sizes():
    ids = np.arange(8, dtype=np.int32)
    costs = np.ones((1, 8))
    for sizes, budgets in ((None, [4]), (np.full(8, 1.5), [4]),
                           (np.ones(8), [2.0 ** 31]), (-np.ones(8), [4])):
        with pytest.raises(ValueError):
            sweep_jax("lru", ids, costs, np.array(budgets), sizes=sizes,
                      budget_unit="bytes")
    with pytest.raises(ValueError):
        sweep_jax("lru", ids, costs, np.array([4]), budget_unit="objects")


def test_byte_budget_free_objects_match_python():
    """Objects that cost nothing to fetch, under a byte budget: a free
    object's cost-Belady ratio s * gap / c stays finite, so no policy's
    score turns NaN, and the grid equals `policies.py` cell by cell."""
    rng = np.random.default_rng(33)
    T, N = 600, 40
    ids = rng.integers(0, N, T).astype(np.int32)
    # up to 64 MB and reused dozens of steps later: s * gap passes the
    # float32 range when divided by a tiny floor
    sizes = 2 ** rng.integers(10, 27, N)
    cm = np.stack([f + sizes * e for f, e in _PRICES[:2]])
    cm[0, ::3] = 0.0
    budgets = np.array([sizes.sum() // 8, sizes.sum() // 3])
    out = sweep_jax(list(POLICY_WEIGHTS), ids, cm, budgets, num_objects=N,
                    sizes=sizes, use_pallas=False, budget_unit="bytes")
    assert np.isfinite(out).all()
    for q, pol in enumerate(POLICY_WEIGHTS):
        for p in range(len(cm)):
            for k, B in enumerate(budgets):
                ref = simulate(pol, Trace(ids=ids, sizes=sizes.astype(float)),
                               cm[p], float(B))
                assert out[q, p, k] == pytest.approx(ref.dollars, rel=1e-6), \
                    (pol, p, k)


def test_byte_budget_loop_ends_without_a_victim():
    """A cell whose victim selection names no victim (here a NaN cost makes
    a NaN score) stops asking for trips: the loop ends, and the other
    cells of the grid still replay exactly."""
    rng = np.random.default_rng(21)
    ids, costs = _rand(rng, 120, 16)
    sizes = _byte_sizes(rng, 16, 24)
    cm = np.stack([costs, costs])
    cm[0, ids[0]] = np.nan
    out = sweep_jax(["lru", "gds"], ids, cm, np.array([24]), num_objects=16,
                    sizes=sizes, use_pallas=False, budget_unit="bytes")
    for q, pol in enumerate(["lru", "gds"]):
        ref = simulate(pol, Trace(ids=ids, sizes=sizes.astype(float)),
                       costs, 24.0)
        assert out[q, 1, 0] == pytest.approx(ref.dollars, rel=1e-6)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_byte_budget_victims_in_the_inserted_tile(use_pallas):
    """Misses whose victims lie in the same (8, 128) tile of the state as
    the object they insert: the update writes that tile back from the
    eviction loop's output, so the victims stay evicted. Most requests go
    to tile 1, a few to tile 0; one object of tile 1 takes the whole
    budget and evicts everything. The trajectory and the totals equal
    `policies.py`, on the kernel and on the jnp path."""
    rng = np.random.default_rng(16)
    N, T, B = 2048, 300, 24
    ids = rng.choice(np.r_[np.arange(1024, 1048), np.arange(8)],
                     T).astype(np.int32)
    costs = 2.0 ** rng.integers(0, 12, N)
    sizes = 2 ** rng.integers(0, 4, N)
    sizes[1030] = B
    nxt = next_use_indices(ids).astype(np.int32)
    for policy in ("lru", "gdsf", "cost_belady"):
        args = (jnp.asarray(ids), jnp.asarray(nxt),
                jnp.asarray(costs, jnp.float32),
                jnp.asarray(sizes, jnp.float32), jnp.asarray(sizes, jnp.int32),
                jnp.asarray(cost_density(costs, sizes)), jnp.int32(B),
                jnp.asarray(POLICY_WEIGHTS[policy].as_array()), N)
        d, h, evictions, bypasses, _, (_, _, ev, _) = _simulate(
            *args, use_pallas=use_pallas, trace_steps=True)
        evicts = np.diff(np.asarray(ev), prepend=0) > 0
        assert (evicts & (ids >= 1024)).any() and (evicts & (ids < 8)).any()
        ref = simulate(policy, Trace(ids=ids, sizes=sizes.astype(float)),
                       costs, float(B))
        assert (int(h), int(evictions), int(bypasses)) == \
            (ref.hits, ref.evictions, 0), policy
        assert float(d) == ref.dollars, policy


@pytest.mark.parametrize("use_pallas", [False, True])
def test_fetch_through_in_one_cell_insert_in_another(use_pallas):
    """At the same step one cell fetches an object through (it is larger
    than that cell's budget) and another inserts it. The fetched-through
    cell's tile keeps its entries, neighbours cached in it included: the
    object misses there every time, and every cell of the grid equals
    `policies.py` in dollars, evictions and fetch-throughs."""
    rng = np.random.default_rng(17)
    N, T = 64, 240
    ids, costs = _rand(rng, T, N)
    ids[::6] = 5
    sizes = 2 ** rng.integers(0, 4, N)
    sizes[5] = 40
    budgets = np.array([24, 64])
    policies = ["lru", "gdsf", "cost_belady"]
    cm = np.stack([costs, 4 * costs])
    args = (jnp.asarray(stack_policy_weights(policies)), jnp.asarray(ids),
            jnp.asarray(next_use_indices(ids).astype(np.int32)),
            jnp.asarray(cm, jnp.float32), jnp.asarray(cost_density(cm, sizes)),
            jnp.asarray(sizes, jnp.float32), jnp.asarray(sizes, jnp.int32),
            jnp.asarray(budgets, jnp.int32))
    d, evictions, bypasses, _ = (np.asarray(x) for x in pj._sweep_grid(
        *args, N, use_pallas))
    np.testing.assert_array_equal(bypasses[:, :, 0], np.sum(ids == 5))
    np.testing.assert_array_equal(bypasses[:, :, 1], 0)
    for q, pol in enumerate(policies):
        for p in range(len(cm)):
            for k, B in enumerate(budgets):
                ref = simulate(pol, Trace(ids=ids, sizes=sizes.astype(float)),
                               cm[p], float(B))
                assert (d[q, p, k], evictions[q, p, k]) == \
                    (np.float32(ref.dollars), ref.evictions), (pol, p, B)

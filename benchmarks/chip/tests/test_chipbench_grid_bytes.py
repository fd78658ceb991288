"""The byte-budget grid cell at test size: a sound run is correct, the
bfloat16 control is not, and a run with the timed path broken underneath
(one victim per miss, no fetch-through, one cell's dollars off) is not.
The program's counts agree with the plain reference's, and every seed of
the CDN stream gets the same work."""
import copy

import jax
import numpy as np
import pytest

import cells
import repro.core.policies_jax as pj
from harness import cdn_gen, spec
from harness.scopes import ms_per_step

CELL = "wikicdn-bytes-grid"
SMALL = {"objectcount": 2048}
SMALL_MIX = {"operationcount": 512, "largest_one_hit_on_fixed_steps": 8}
BIG = 2 ** 33 + 5
REAL_SWEEP, REAL_EVICT = pj.sweep_jax, pj._evict_until_fits


def small_cell() -> spec.Cell:
    cell = spec.load_cell(CELL, cells.ROOT / "BENCHMARK.json")
    cell.config = dict(copy.deepcopy(cell.config), **SMALL)
    cell.traffic = dict(copy.deepcopy(cell.traffic), **SMALL_MIX)
    return cell


def _run(seed=BIG):
    return cells.entry("run").run(cells.args(CELL, seed=seed, seconds=0.5),
                                  gate=cells.cpu_gate, cell=small_cell())


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"setup_s", "grid_cellreq_per_s"}
    assert set(res["checks"]) == {"dollar_rel_err", "dollar_rel_err.gdsf",
                                  "window_compiles"}


def test_bfloat16_control_fails_each_limit():
    control = cells.entry("control")
    cell = small_cell()
    limits = cell.config["limits"]
    for row in control.readings(cell, [1, 2, 3], 0.2, gate=cells.cpu_gate):
        assert set(row["control"]) == set(limits)
        assert all(row["program"][n] <= limits[n] for n in limits)
        assert all(row["control"][n] > limits[n] for n in limits)


def _one_trip(cond, body, init):
    return jax.lax.cond(cond(init), body, lambda c: c, init)


def _one_victim_per_miss(*a, **k):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.lax, "while_loop", _one_trip)
        return REAL_EVICT(*a, **k)


def _never_fetch_through(select, cached, used, infl, miss, size, capacity,
                         *a):
    """Every miss goes in: a larger object than the cache evicts all."""
    return REAL_EVICT(select, cached, used, infl, miss,
                      jax.numpy.minimum(size, capacity), capacity, *a)


def _answer_altered(*a, **k):
    out = np.array(REAL_SWEEP(*a, **k))
    out.flat[5] *= 1.01
    return out


@pytest.mark.parametrize("fault", [_one_victim_per_miss,
                                   _never_fetch_through, _answer_altered])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    if fault is _answer_altered:
        monkeypatch.setattr(pj, "sweep_jax", fault)
    else:
        monkeypatch.setattr(pj, "_evict_until_fits", fault)
        monkeypatch.setattr(pj, "_EXECUTABLES", {})
        jax.clear_caches()
    try:
        res = _run()
    finally:
        jax.clear_caches()
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("use_pallas", [False, True])
def test_program_counts_match_the_reference(use_pallas):
    """Dollars, evictions and fetch-throughs of every cell of one answer,
    on the jnp path and on the kernel in interpret mode (two policies, for
    time), equal the plain reference's."""
    from repro.obs import Tracer
    cell = small_cell()
    drv = cell.driver()
    s = drv.State()
    s.cell = cell
    drv._catalog(s, 11)
    policies = ["gdsf", "cost_belady"] if use_pallas else \
        list(cell.config["grid"]["policies"])
    ids = s.keys.segment(cell.traffic, 11, 0)
    tracer = Tracer()
    got = pj.sweep_jax(policies, ids, s.costs, s.budgets,
                       num_objects=SMALL["objectcount"], sizes=s.sizes,
                       use_pallas=use_pallas, tracer=tracer,
                       budget_unit="bytes")
    want, evictions, bypasses = cell.reference().grid(
        ids, s.costs, s.sizes, s.budgets, policies, counts=True)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    attrs = tracer.spans(name="replay.execute")[-1].attrs
    assert attrs["evictions"] == evictions.sum() > 0
    assert attrs["bypasses"] == bypasses.sum() > 0
    assert attrs["evict_trips"] >= evictions.max()


def test_eviction_loop_readers(monkeypatch):
    """The loop's device time reads the program's `replay.evict_bytes`
    scope, in a grid of either unit, and is silent where the program has
    no such scope; the trips per step read the run's counters."""
    trips = spec.metric_reader("grid.evict_trips_per_step")
    ms = spec.metric_reader("grid.evict_bytes_ms_per_step")

    class Trace:
        def __init__(self):
            self.ops = {}

    class Run:
        def __init__(self, counters):
            self.counters, self.trace = counters, Trace()

    run = Run({"scan_steps_traced": 400, "evict_trips_traced": 1000})
    assert trips(run) == 2.5
    assert trips(Run({"scan_steps_traced": 400})) is None
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 40, 200).astype(np.int32)
    costs = np.ones((1, 40))
    for unit in ("bytes", "pages"):
        pj._EXECUTABLES.clear()
        pj.sweep_jax("lru", ids, costs, np.array([9]), num_objects=40,
                     sizes=np.full(40, 3), use_pallas=False,
                     budget_unit=unit)
        names = pj.step_scopes()["replay.evict_bytes"]
        assert names
        run.trace.ops = {n: [1, 2e8 / len(names)] for n in names}
        assert ms(run) == pytest.approx(0.5)
        assert ms_per_step(run, "replay.evict_bytes") == ms(run)
    monkeypatch.setattr(pj, "step_scopes", lambda: {"replay.victim": names})
    assert ms(run) is None


CONFIG = spec.load_cell(CELL, cells.ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("size", [(SMALL, SMALL_MIX), ({}, {})],
                         ids=["test_size", "cell_size"])
def test_every_seed_gets_the_same_work(size):
    """Each seed and each answer requests the same multiset of (size,
    request count) pairs, in another order and on other keys, with the
    largest one-hit objects at the same steps; the catalog's bytes are the
    same for every seed."""
    config = dict(CONFIG.config, **size[0])
    traffic = dict(CONFIG.traffic, **size[1])
    T = traffic["operationcount"]
    pairs, catalogs, answers, largest = [], set(), [], []
    for seed in (1, 2, BIG):
        st = cdn_gen.Stream(config, traffic, seed)
        catalogs.add(st.catalog_bytes)
        for k in (0, 1):
            ids = st.segment(traffic, seed, k)
            assert ids.dtype == np.int32 and len(ids) == T
            keys, n = np.unique(ids, return_counts=True)
            pairs.append(sorted(zip(st.sizes[keys], n)))
            answers.append(ids)
            largest.append(st.sizes[ids[st.steps]])
        assert np.array_equal(st.segment(traffic, seed, 0), cdn_gen.Stream(
            config, traffic, seed).segment(traffic, seed, 0))
    assert len(catalogs) == 1
    assert all(p == pairs[0] for p in pairs)
    assert all(np.array_equal(x, largest[0]) for x in largest)
    assert largest[0][0] == max(st.sizes[ids]) and np.all(
        largest[0][:-1] >= largest[0][1:])
    assert len(set(st.steps)) == traffic["largest_one_hit_on_fixed_steps"]
    assert not np.array_equal(answers[0], answers[1])
    assert not np.array_equal(answers[0], answers[2])
    law = config["size_law"]
    sizes = cdn_gen.sizes_by_rank(config, traffic)
    counts = cdn_gen.request_counts(config, traffic)
    assert counts.sum() == T
    assert counts @ sizes / T == pytest.approx(
        law["access_weighted_mean_bytes"], rel=1e-3)
    assert sizes.min() >= law["scale_bytes"] and sizes.max() <= law["max_bytes"]
    if not size[0]:      # at the cell's size the law reaches the traces' max
        assert sizes.max() == law["max_bytes"]
    assert np.all(sizes == np.round(sizes))
    # a third of the requests are one-hit wonders, the largest object too
    once = round(traffic["one_hit_share_of_requests"] * T)
    n_core = round(traffic["core_share_of_objects"] * len(sizes))
    assert counts[n_core:].sum() == once and counts[n_core:].max() == 1
    assert counts[-1] == 1

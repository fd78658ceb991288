"""Online dollar-governance subsystem: shadow panel, windowed audit,
s*-aware admission, governor hot-swap, per-consumer billing attribution."""
import numpy as np
import pytest

from repro.core import Trace, simulate
from repro.core.pricing import PRICE_VECTORS, PriceVector
from repro.egress import EgressCache, ObjectStore
from repro.obs import EventLog, MetricsRegistry
from repro.online import (DollarGovernor, SStarAdmission, ShadowCache,
                          ShadowPanel, WindowedAuditor, preferred_policy)
from repro.online.scenario import (EGRESS_HEAVY, FEE_HEAVY,
                                   regime_shift_scenario, run_fixed,
                                   run_governed)

ONLINE = ("lru", "lfu", "gds", "gdsf")


def _uniform_store(price="s3_internet", n=32, size=4096):
    store = ObjectStore(price)
    for i in range(n):
        store.put(f"o{i}", bytes(size))
    return store


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_roundtrip(tmp_path):
    m = MetricsRegistry()
    m.inc("a")
    m.inc("a", 2)
    m.set_gauge("g", 1.5)
    m.observe("s", 0.1, step=10)
    m.observe("s", 0.2, step=20)
    assert m.counter("a") == 3
    assert m.latest("s") == pytest.approx(0.2)
    snap = m.snapshot()
    assert snap["gauges"]["g"] == 1.5
    assert snap["series"]["s"] == [[10, 0.1], [20, 0.2]]
    p = m.write_json(tmp_path / "metrics.json")
    import json
    assert json.loads(p.read_text())["counters"]["a"] == 3


# ---------------------------------------------------------------------------
# per-consumer billing attribution (audit satellite)
# ---------------------------------------------------------------------------

def test_audit_excludes_other_consumers():
    store = _uniform_store()
    cache = EgressCache(store, 8 * 4096, "lru", consumer="mine")
    for i in range(16):
        cache.get(f"o{i}")
    # another consumer hammers the store directly: must NOT pollute audit
    for _ in range(50):
        store.get("o0", consumer="other")
    rep = cache.audit()
    assert rep.observed_dollars == pytest.approx(cache.meter.dollars)
    assert store.meter.dollars > rep.observed_dollars
    assert store.meter_for("other").gets == 50


def test_consumer_dollars_sum_to_store_total():
    store = _uniform_store()
    a = EgressCache(store, 4 * 4096, "lru", consumer="a")
    b = EgressCache(store, 4 * 4096, "gdsf", consumer="b")
    rng = np.random.default_rng(0)
    for i in rng.integers(0, 32, 300):
        (a if i % 2 else b).get(f"o{i}")
    per = store.consumer_snapshot()
    assert set(per) == {"a", "b"}
    assert sum(m["dollars"] for m in per.values()) == \
        pytest.approx(store.meter.dollars)


def test_audit_budget_grid_one_sweep():
    store = _uniform_store()
    cache = EgressCache(store, 4 * 4096, "lru")
    rng = np.random.default_rng(1)
    for i in rng.zipf(1.2, 400) % 32:
        cache.get(f"o{i}")
    rep = cache.audit(budget_grid=[1, 2, 8, 16])
    assert rep.opt_by_budget is not None
    assert set(rep.opt_by_budget) >= {1, 2, 8, 16}
    # exact OPT-dollars are non-increasing in budget
    ds = [rep.opt_by_budget[b] for b in sorted(rep.opt_by_budget)]
    assert all(x >= y - 1e-12 for x, y in zip(ds, ds[1:]))
    # the bracket refers to the cache's own budget (4 pages), also in the grid
    assert rep.opt_dollars_lower == pytest.approx(rep.opt_by_budget[4])


def test_repricing_accrues_not_rewrites():
    store = ObjectStore("s3_internet")
    store.put("k", bytes(1000))
    store.get("k")
    d1 = store.meter.dollars
    pv = PRICE_VECTORS["s3_internet"]
    assert d1 == pytest.approx(float(pv.miss_cost(1000)))
    store.set_price("gcs_internet")
    store.get("k")
    pv2 = PRICE_VECTORS["gcs_internet"]
    assert store.meter.dollars == pytest.approx(
        d1 + float(pv2.miss_cost(1000)))


# ---------------------------------------------------------------------------
# shadow panel
# ---------------------------------------------------------------------------

def test_shadow_panel_bills_zero_egress():
    store = _uniform_store()
    cache = EgressCache(store, 8 * 4096, "lru", consumer="live")
    panel = ShadowPanel(cache.capacity, ONLINE)
    cache.add_listener(panel.on_event)
    rng = np.random.default_rng(2)
    for i in rng.integers(0, 32, 500):
        cache.get(f"o{i}")
    # every billed dollar is attributed to the live cache; shadows are free
    assert set(store.consumer_snapshot()) == {"live"}
    assert store.meter.dollars == pytest.approx(cache.meter.dollars)
    # yet the panel DID account counterfactual dollars
    assert all(d > 0 for d in panel.dollars().values())


@pytest.mark.parametrize("law", ["uniform", "variable"])
@pytest.mark.parametrize("policy", ONLINE)
def test_live_shadow_and_oracle_agree(policy, law):
    """One trace through the live cache, a shadow of the same policy on its
    listener, and the independent oracle `core.policies.simulate`: equal
    hits, misses and evictions, and the same dollars."""
    rng = np.random.default_rng(3)
    n = 24
    if law == "uniform":
        sizes, capacity = np.full(n, 2048), 5 * 2048
    else:   # 512 B to 16 KiB (32x); the 16 KiB objects exceed the budget
        sizes, capacity = 512 * 2 ** rng.integers(0, 6, n), 12_000
    store = ObjectStore("s3_internet")
    for i, s in enumerate(sizes):
        store.put(f"o{i}", bytes(int(s)))
    ids = (rng.zipf(1.3, 600) % n).astype(np.int32)
    events = EventLog(10_000)
    cache = EgressCache(store, capacity, policy, events=events)
    shadow = ShadowCache(policy, capacity)
    victims = []                 # per shadow insert, its victims in order
    insert = shadow.insert

    def spy(*args):
        out = insert(*args)
        victims.append([key for key, _, _ in out])
        return out

    shadow.insert = spy
    cache.add_listener(
        lambda ev: shadow.access(ev.key, ev.nbytes, ev.miss_cost))
    for i in ids:
        cache.get(f"o{i}")
    ref = simulate(policy, Trace(ids=ids, sizes=sizes.astype(np.float64)),
                   store.price.miss_cost(sizes), float(capacity))

    assert (cache.hits, cache.misses) == (shadow.hits, shadow.misses) \
        == (ref.hits, ref.misses)
    live_victims = [ev.key for ev in events.events("evict")]
    assert live_victims == [key for v in victims for key in v]
    assert len(live_victims) == ref.evictions > 0
    assert shadow.dollars == cache.meter.dollars
    assert cache.meter.dollars == pytest.approx(ref.dollars, rel=1e-12, abs=0)
    if law == "variable":
        assert sizes.max() >= 8 * sizes.min()
        assert any(sizes[i] > capacity for i in ids)       # fetch-through
        assert max(map(len, victims)) >= 2      # evict-until-fits looped


@pytest.mark.parametrize("dollars,incumbent,expected", [
    ({"lru": 1.0, "gdsf": 0.8}, "lru", "gdsf"),    # undercuts by > 10%
    ({"lru": 1.0, "gdsf": 0.95}, "lru", "lru"),    # undercuts by < 10%
    ({"gds": 0.5, "gdsf": 0.2}, "lru", "lru"),     # incumbent absent
    ({"gdsf": 0.0, "lru": 0.0}, "lru", "lru"),     # incumbent at $0
    ({"lru": 0.3, "gdsf": 0.5}, "lru", "lru"),     # best is the incumbent
    ({}, "lfu", "lfu"),                            # no evidence
])
def test_preferred_policy(dollars, incumbent, expected):
    assert preferred_policy(dollars, incumbent, 0.1) == expected


# ---------------------------------------------------------------------------
# windowed audit
# ---------------------------------------------------------------------------

def test_window_ring_buffer_caps_length():
    store = _uniform_store()
    cache = EgressCache(store, 8 * 4096, "lru")
    aud = WindowedAuditor(cache.capacity, window=64)
    cache.add_listener(aud.on_event)
    for i in range(200):
        cache.get(f"o{i % 32}")
    assert len(aud) == 64


def test_window_audit_uniform_exact_sweep():
    store = _uniform_store()
    cache = EgressCache(store, 4 * 4096, "lru")
    m = MetricsRegistry()
    aud = WindowedAuditor(cache.capacity, window=256,
                          budget_grid=[2, 4, 8], metrics=m)
    cache.add_listener(aud.on_event)
    rng = np.random.default_rng(4)
    for i in rng.zipf(1.2, 400) % 32:
        cache.get(f"o{i}")
    rep = aud.audit()
    assert rep.uniform
    assert rep.opt_dollars_lower == rep.opt_dollars_upper  # exact, not bracket
    assert rep.observed_dollars >= rep.opt_dollars_lower - 1e-12
    assert rep.dollar_regret >= 0
    ds = [rep.opt_by_budget[b] for b in sorted(rep.opt_by_budget)]
    assert all(x >= y - 1e-12 for x, y in zip(ds, ds[1:]))
    assert m.latest("online.window_regret") == pytest.approx(rep.dollar_regret)


def test_window_audit_variable_sizes_bracket():
    store = ObjectStore("gcs_internet")
    rng = np.random.default_rng(5)
    sizes = rng.integers(500, 50_000, 16)
    for i, s in enumerate(sizes):
        store.put(f"o{i}", bytes(int(s)))
    cache = EgressCache(store, 60_000, "gdsf")
    aud = WindowedAuditor(cache.capacity, window=256)
    cache.add_listener(aud.on_event)
    for i in rng.integers(0, 16, 250):
        cache.get(f"o{i}")
    rep = aud.audit()
    assert not rep.uniform
    assert rep.opt_dollars_lower <= rep.opt_dollars_upper + 1e-12
    assert rep.observed_dollars >= rep.opt_dollars_lower - 1e-12


def test_empty_window_audit_is_none():
    aud = WindowedAuditor(1000, window=16)
    assert aud.audit() is None


def test_window_audit_tolerates_out_of_order_event_times():
    """Events delivered out of event-time order (within the skew bound)
    audit identically to the same events delivered sorted: the buffer
    insorts by event time through the shared Watermark helper."""
    from repro.egress.cache import AccessEvent
    rng = np.random.default_rng(11)
    evs = [AccessEvent(f"o{i % 7}", 4096, bool(i % 3), 0.001 * (i % 5 + 1),
                       "lru", i, float(i)) for i in range(120)]
    # bounded shuffle: displace each event by < max_skew positions
    skewed = list(evs)
    for i in range(0, len(skewed) - 4, 4):
        seg = skewed[i:i + 4]
        rng.shuffle(seg)
        skewed[i:i + 4] = seg
    ordered, jumbled = (WindowedAuditor(8 * 4096, window=64, max_skew=8.0)
                        for _ in range(2))
    for ev in evs:
        ordered.on_event(ev)
    for ev in skewed:
        jumbled.on_event(ev)
    assert jumbled.watermark.late > 0          # the shuffle did something
    a, b = ordered.audit(), jumbled.audit()
    assert (a.observed_dollars, a.opt_dollars_lower, a.requests) == \
        (b.observed_dollars, b.opt_dollars_lower, b.requests)
    # beyond the bound the clock model is broken, not merely late
    strict = WindowedAuditor(8 * 4096, window=64, max_skew=2.0)
    strict.on_event(evs[50])
    with pytest.raises(ValueError):
        strict.on_event(evs[10])


# ---------------------------------------------------------------------------
# s*-aware admission
# ---------------------------------------------------------------------------

def test_sstar_admission_rules():
    pv = PriceVector("t", get_fee=1e-6, egress_per_byte=1e-9)  # s* = 1000 B
    adm = SStarAdmission(pv, capacity_bytes=100_000,
                         large_object_frac=0.5)
    assert adm.admit("a", 500, 1)          # below s*: always keep
    assert not adm.admit("b", 60_000, 5)   # > 50% of capacity: never
    assert not adm.admit("c", 5_000, 1)    # egress-dominated, first touch
    assert adm.admit("c", 5_000, 2)        # ... admitted on reuse
    assert adm.admitted == 2 and adm.bypassed == 2


def test_admission_plugged_into_cache_bypasses():
    store = ObjectStore(PriceVector("t", get_fee=1e-6, egress_per_byte=1e-9))
    store.put("small", bytes(500))
    store.put("mid", bytes(5_000))
    adm = SStarAdmission(store, capacity_bytes=100_000)
    cache = EgressCache(store, 100_000, "lru", admission=adm)
    cache.get("small")
    assert cache.get("small")  # resident: admitted below s*
    assert store.meter.gets == 1
    cache.get("mid")           # first touch: bypassed (fetch-through)
    assert cache.bypasses == 1
    cache.get("mid")           # second touch: missed again, now admitted
    assert store.meter.gets == 3
    cache.get("mid")
    assert store.meter.gets == 3  # resident now


def test_admission_tracks_price_flip():
    store = ObjectStore(FEE_HEAVY)          # s* = 10 MB: everything admitted
    store.put("obj", bytes(50_000))
    adm = SStarAdmission(store, capacity_bytes=10_000_000)
    assert adm.admit("obj", 50_000, 1)
    store.set_price(EGRESS_HEAVY)           # s* = 10 B: now on probation
    assert not adm.admit("obj2", 50_000, 1)


# ---------------------------------------------------------------------------
# governor + regime shift (acceptance)
# ---------------------------------------------------------------------------

def test_policy_hot_swap_preserves_contents_and_bill():
    store = _uniform_store()
    cache = EgressCache(store, 8 * 4096, "lru")
    for i in range(8):
        cache.get(f"o{i}")
    resident = cache._machine.resident()
    bill = cache.meter.dollars
    cache.set_policy("gdsf")
    assert cache._machine.resident() == resident
    assert cache.used == sum(len(v) for v in resident.values())
    assert cache.meter.dollars == bill          # the swap itself bills $0
    for i in range(8):
        cache.get(f"o{i}")                      # all hits: still unbilled
    assert cache.meter.dollars == bill
    assert cache.policy_swaps == 1


def test_governor_swaps_toward_cheaper_shadow():
    """LFU start on a drifting working set: the governor must leave LFU."""
    store = ObjectStore(FEE_HEAVY)
    for i in range(200):
        store.put(f"o{i}", bytes(1024))
    cache = EgressCache(store, 20 * 1024, "lfu", consumer="live")
    gov = DollarGovernor(cache, window=100, hysteresis=0.05)
    rng = np.random.default_rng(6)
    base = 0
    for step in range(1200):
        if step and step % 150 == 0:
            base += 10                      # working set drifts: LFU stales
        cache.get(f"o{base + int(rng.integers(12))}")
    assert cache.policy != "lfu"
    assert len(gov.swaps) >= 1
    assert gov.swaps[0].old_policy == "lfu"


def test_regime_shift_governor_within_10pct_of_best_fixed():
    """The ISSUE's acceptance criterion: price vector flipped across s*
    mid-trace; governed realized dollars within 10% of the best fixed
    policy in hindsight; shadow panel bills $0 of extra egress."""
    sc = regime_shift_scenario(n_phase=3000, seed=0)
    fixed = {p: run_fixed(sc, p)["dollars"] for p in ONLINE}
    best_policy = min(fixed, key=lambda p: fixed[p])
    m = MetricsRegistry()
    gov_res, gov = run_governed(sc, metrics=m)
    assert gov_res["dollars"] <= 1.10 * fixed[best_policy], \
        (gov_res, fixed)
    # the governor actually adapted (regime shift = at least one swap)
    assert len(gov_res["swaps"]) >= 1
    # shadow panel billed $0 extra egress: every store dollar is attributed
    # to the governed cache's own consumer meter, and to nothing else
    store_dollars = gov.cache.store.meter.dollars
    per_consumer = gov.cache.store.consumer_snapshot()
    assert set(per_consumer) == {"governed"}
    assert per_consumer["governed"]["dollars"] == pytest.approx(store_dollars)
    # metrics saw the swaps and the per-policy window series
    assert m.counter("governor.swaps") == len(gov_res["swaps"])
    assert any(k.startswith("governor.window_dollars.") for k in m.series)


def test_regime_shift_phase_winners_flip():
    """The scenario really is a regime shift: the per-phase winner changes
    across the price flip (recency wins fee-dominated, cost-awareness wins
    egress-dominated)."""
    sc = regime_shift_scenario(n_phase=3000, seed=0)
    phase = {}
    for p in ("lru", "gdsf"):
        store = sc.make_store()
        cache = EgressCache(store, sc.capacity_bytes, p, consumer="x")
        ph1 = None
        for t, key in enumerate(sc.keys):
            if t == sc.flip_at:
                store.set_price(sc.price_b)
                ph1 = cache.meter.dollars
            cache.get(key)
        phase[p] = (ph1, cache.meter.dollars - ph1)
    assert phase["lru"][0] < phase["gdsf"][0]    # fee phase: LRU cheaper
    assert phase["gdsf"][1] < phase["lru"][1]    # egress phase: GDSF cheaper

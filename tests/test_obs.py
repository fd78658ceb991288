"""Observability layer (DESIGN.md §9): span tracer, decision event log,
histograms/Prometheus, solver profiling — and the billing-faithfulness
acceptance: summed span dollars == the consumer's BillingMeter total."""
import json
import re

import numpy as np
import pytest

from repro.egress.cache import EgressCache
from repro.egress.store import ObjectStore
from repro.obs import (EVENT_KINDS, EventLog, MetricsRegistry, NullTracer,
                       Tracer, log_bounds, regime_tag, sstar_bounds, validate)

# ---------------------------------------------------------------------------
# tracer


def test_span_nesting_parent_ids():
    t = Tracer()
    with t.span("a") as a:
        with t.span("b") as b:
            with t.span("c") as c:
                pass
    spans = {s.name: s for s in t.spans()}
    assert spans["a"].parent_id is None
    assert spans["b"].parent_id == spans["a"].span_id
    assert spans["c"].parent_id == spans["b"].span_id
    # closed innermost-first (complete events)
    assert [s.name for s in t.spans()] == ["c", "b", "a"]
    assert all(s.dur >= 0 for s in t.spans())


def test_span_begin_end_fast_path_matches_with():
    t = Tracer()
    sp = t.begin("outer", "cat1")
    inner = t.begin("inner", "cat1")
    t.end(inner)
    t.end(sp)
    assert inner.parent_id == sp.span_id
    assert t.spans(cat="cat1", name="inner")[0] is inner


def test_tracer_ring_is_bounded():
    t = Tracer(max_spans=10)
    for i in range(25):
        with t.span(f"s{i}"):
            pass
    assert len(t.spans()) == 10
    assert t.dropped == 15
    assert [s.name for s in t.spans()] == [f"s{i}" for i in range(15, 25)]


def test_dollars_query_fsum_with_filters():
    t = Tracer()
    for consumer, d in [("a", 0.1), ("a", 0.2), ("b", 0.4)]:
        with t.span("store.get", cat="store", consumer=consumer) as sp:
            sp.set(dollars=d)
    assert t.dollars(name="store.get", consumer="a") == pytest.approx(0.3)
    assert t.dollars() == pytest.approx(0.7)


def test_chrome_trace_round_trips_json():
    t = Tracer()
    with t.span("req", cat="serve", rid=7):
        with t.span("get", cat="cache") as sp:
            sp.set(bytes=123, dollars=1e-6)
    blob = json.dumps(t.to_chrome_trace())
    doc = json.loads(blob)
    evs = doc["traceEvents"]
    assert len(evs) == 2 and doc["displayTimeUnit"] == "ms"
    for ev in evs:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
        assert {"name", "cat", "pid", "tid", "args"} <= set(ev)
    get = next(e for e in evs if e["name"] == "get")
    req = next(e for e in evs if e["name"] == "req")
    assert get["args"]["parent_id"] == req["args"]["span_id"]
    assert get["args"]["dollars"] == 1e-6


def test_null_tracer_is_falsy_noop():
    nt = NullTracer()
    assert not nt
    with nt.span("x", whatever=1) as sp:
        sp.set(more=2)
    sp2 = nt.begin("y")
    nt.end(sp2)
    assert nt.spans() == [] and nt.dollars() == 0.0
    assert not Tracer(enabled=False)


def test_regime_tag_crossover():
    assert regime_tag(100, 4444.4) == "fee_dominated"
    assert regime_tag(4444.4, 4444.4) == "fee_dominated"   # boundary: fee side
    assert regime_tag(10_000, 4444.4) == "egress_dominated"


# ---------------------------------------------------------------------------
# decision event log


def test_event_log_ring_bounded_totals_survive():
    log = EventLog(capacity=8)
    for i in range(20):
        log.record("miss", f"k{i}", 100, 0.5, 0.5, i, "gdsf")
    assert len(log) == 8
    assert log.dropped == 12
    assert log.counts["miss"] == 20                 # lifetime, not window
    assert log.dollars_billed("miss") == pytest.approx(10.0)
    assert log.dollars_at_stake("miss") == pytest.approx(10.0)
    assert [e.key for e in log.events("miss")] == [f"k{i}" for i in range(12, 20)]
    assert log.events("hit") == []


def test_event_log_snapshot_round_trips():
    log = EventLog(capacity=16)
    log.record("hit", "a", 10, 0.0, 2.0, 1, "lru")
    log.record("policy_swap", "", 0, 0.0, 0.0, 2, "gdsf")
    snap = json.loads(log.to_json())
    assert snap["recorded"] == 2 and snap["dropped"] == 0
    assert snap["counts"]["hit"] == 1
    assert [e["kind"] for e in snap["window"]] == ["hit", "policy_swap"]
    assert set(snap["window"][0]) == {"kind", "key", "nbytes", "dollar_delta",
                                      "dollars_at_stake", "clock", "policy"}
    assert all(k in EVENT_KINDS for k in snap["counts"])


# ---------------------------------------------------------------------------
# metrics / histograms / Prometheus

_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{le="[^"]+"\})? [-+0-9.einfa]+$')


def test_histogram_buckets_and_cumulative():
    m = MetricsRegistry()
    for v in [0.5, 1.0, 3.0, 100.0]:
        m.observe_hist("h", v, bounds=[1.0, 10.0])
    h = m.hist("h")
    assert h.counts == [2, 1, 1]        # <=1, <=10, +Inf overflow
    assert h.cumulative() == [2, 3, 4]
    assert h.count == 4 and h.sum == pytest.approx(104.5)


def test_sstar_bounds_centered_on_crossover():
    sstar = 4444.444
    b = sstar_bounds(sstar, octaves=2)
    assert b == pytest.approx([sstar / 4, sstar / 2, sstar, 2 * sstar,
                               4 * sstar])
    assert log_bounds(1e-3, 1e0, per_decade=1) == pytest.approx(
        [1e-3, 1e-2, 1e-1, 1e0])


def test_prometheus_exposition_parses():
    m = MetricsRegistry()
    m.inc("egress.cache-1.hits", 3)
    m.set_gauge("governor/policy", 1.0)
    m.observe("online.window_regret", 0.25, step=10)
    m.observe_hist("egress.get_dollars", 2e-6, bounds=[1e-6, 1e-3])
    text = m.to_prometheus()
    lines = text.strip().split("\n")
    assert lines, "empty exposition"
    for ln in lines:
        assert ln.startswith("# TYPE ") or _PROM_LINE.match(ln), ln
    # histogram: cumulative buckets, +Inf == _count, names sanitized
    assert 'egress_get_dollars_bucket{le="1e-06"} 0' in lines
    assert 'egress_get_dollars_bucket{le="0.001"} 1' in lines
    assert 'egress_get_dollars_bucket{le="+Inf"} 1' in lines
    assert "egress_get_dollars_count 1" in lines
    assert "egress_cache_1_hits 3.0" in lines
    assert "online_window_regret_last 0.25" in lines


# ---------------------------------------------------------------------------
# egress wiring: spans + events + histograms off one live cache


def _replay(tracer=None, events=None, metrics=None):
    store = ObjectStore("s3_internet", tracer=tracer)
    for i in range(8):
        store.put(f"o{i}", bytes(1000 * (i + 1)))
    cache = EgressCache(store, capacity_bytes=6000, policy="gdsf",
                        consumer="obs_test", metrics=metrics, tracer=tracer,
                        events=events)
    rng = np.random.default_rng(0)
    for i in rng.integers(0, 8, 200):
        cache.get(f"o{i}")
    return store, cache


def test_span_dollars_equal_meter_on_egress_replay():
    tracer = Tracer()
    store, cache = _replay(tracer=tracer)
    got = tracer.dollars(name="store.get", consumer="obs_test")
    assert got == pytest.approx(cache.meter.dollars, rel=1e-12)
    assert got > 0
    # store.get spans nest under the cache.get span of the same key
    cache_by_id = {s.span_id: s for s in tracer.spans(name="cache.get")}
    store_spans = tracer.spans(name="store.get")
    assert len(store_spans) == cache.misses
    for sp in store_spans:
        parent = cache_by_id[sp.parent_id]
        assert parent.attrs["key"] == sp.attrs["key"]
        assert parent.attrs["hit"] is False
        assert sp.attrs["regime"] == regime_tag(
            sp.attrs["bytes"], store.price.crossover_bytes)


def test_event_log_miss_dollars_bit_equal_meter():
    events = EventLog()
    store, cache = _replay(events=events)
    # same-order naive accrual: not approx — bit-equal to the meter
    assert events.dollars_billed("miss") == cache.meter.dollars
    assert events.counts["hit"] == cache.hits
    assert events.counts["miss"] == cache.misses
    assert events.counts["admit"] + events.counts["reject"] == cache.misses
    assert events.counts["evict"] > 0
    cache.set_policy("lru")
    assert events.events("policy_swap")[-1].policy == "lru"
    # hits bill nothing; at-stake is what the hit saved
    assert events.dollars_billed("hit") == 0.0
    assert events.dollars_at_stake("hit") > 0


def test_size_histogram_centered_on_sstar():
    m = MetricsRegistry()
    store, cache = _replay(metrics=m)
    h = m.hist("egress.obs_test.object_bytes")
    assert h is not None
    assert h.count == cache.hits + cache.misses
    sstar = store.price.crossover_bytes
    assert any(b == pytest.approx(sstar) for b in h.bounds)
    d = m.hist("egress.obs_test.get_dollars")
    assert d.count == cache.misses
    assert d.sum == pytest.approx(cache.meter.dollars, rel=1e-9)


def test_disabled_publishers_publish_nothing():
    tracer = NullTracer()
    events = None
    store, cache = _replay(tracer=tracer, events=events)
    assert tracer.to_dicts() == []
    assert cache.meter.dollars > 0          # billing unaffected


# ---------------------------------------------------------------------------
# solver profiling hooks


def test_opt_exact_profile_counters():
    from repro.core import exact_opt_uniform, exact_opt_uniform_sweep
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 12, 300).astype(np.int32)
    costs = rng.uniform(0.5, 2.0, 12)
    r = exact_opt_uniform(ids, costs, 4)
    p = r.profile
    assert p["dijkstra_calls"] >= 1
    assert p["augmentations"] >= p["dijkstra_calls"] - 1
    assert p["paid_intervals"] > 0 and p["nodes"] > 0
    grid = np.array([1, 2, 4, 8])
    s = exact_opt_uniform_sweep(ids, costs, grid)
    sp = s.profile
    assert sp["budgets_answered"] == len(grid)
    # warm start: one parametric run answers the whole grid — far fewer
    # Dijkstra calls than solving each budget from scratch
    assert sp["dijkstra_calls"] < len(grid) * max(1, p["dijkstra_calls"])


def test_sweep_jax_profile_compile_execute_split():
    """sweep_jax's spans: the first call of a shape lowers and compiles
    between transfer and execute; a second call reuses the executable."""
    from repro.core.policies_jax import sweep_jax
    rng = np.random.default_rng(4)
    ids = rng.integers(0, 20, 200).astype(np.int32)
    cost_matrix = np.stack([rng.uniform(0.5, 2.0, 20) for _ in range(2)])
    budgets = np.array([2, 4])
    tracer = Tracer()
    out = sweep_jax("gdsf", ids, cost_matrix, budgets, num_objects=20,
                    tracer=tracer)
    first = [sp.name for sp in tracer.spans()]
    assert first == ["replay.prepare", "replay.transfer", "replay.lower",
                     "replay.compile", "replay.execute", "replay.fetch"]
    assert all(sp.cat == "replay" and sp.dur >= 0 for sp in tracer.spans())
    compile_attrs = tracer.spans(name="replay.compile")[0].attrs
    assert compile_attrs["cells"] == out.size == 4
    assert compile_attrs["steps"] == 200
    assert compile_attrs["mosaic_kernels"] == 0
    again = sweep_jax("gdsf", ids, cost_matrix, budgets, num_objects=20,
                      tracer=tracer)
    assert [sp.name for sp in tracer.spans()][len(first):] == [
        "replay.prepare", "replay.transfer", "replay.execute",
        "replay.fetch"]
    untraced = sweep_jax("gdsf", ids, cost_matrix, budgets, num_objects=20)
    np.testing.assert_array_equal(out, again)
    np.testing.assert_array_equal(out, untraced)


def test_tracer_span_is_a_host_event_of_a_profiler_capture(tmp_path):
    """With a capture on, a span is a host event of the profiler's own
    trace, nested in the annotation around it, on the profiler's clock."""
    import glob

    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((256, 256))
    f(x).block_until_ready()
    tracer = Tracer()
    with tracer.span("before.capture"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("outer.window"):
            with tracer.span("replay.execute", cat="replay"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {ev.name: (ev.start_ns, ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert "before.capture" not in host
    (s, d), (ws, wd) = host["replay.execute"], host["outer.window"]
    assert ws <= s and s + d <= ws + wd
    sp, = tracer.spans(name="replay.execute")
    assert d / 1e9 == pytest.approx(sp.dur, rel=0.5, abs=2e-3)


# ---------------------------------------------------------------------------
# schema validator + exported snapshot shape


def test_schema_validator_accepts_and_rejects():
    schema = {"type": "object", "required": ["a"],
              "properties": {"a": {"type": "integer", "minimum": 0},
                             "b": {"enum": ["x", "y"]}},
              "additionalProperties": False}
    assert validate({"a": 1, "b": "x"}, schema) == []
    errs = validate({"a": -1, "b": "z", "c": 0}, schema)
    assert len(errs) == 3
    assert validate({"b": "x"}, schema)          # missing required
    assert validate({"a": True}, schema)         # bool is not a JSON integer


def test_governance_snapshot_validates_against_checked_in_schema(tmp_path):
    import pathlib
    tracer, events, metrics = Tracer(), EventLog(), MetricsRegistry()
    store = ObjectStore("s3_internet", tracer=tracer)
    for i in range(4):
        store.put(f"o{i}", bytes(2000))
    cache = EgressCache(store, 4000, "gdsf", consumer="snap",
                        metrics=metrics, tracer=tracer, events=events)
    for i in [0, 1, 0, 2, 3, 0, 1]:
        cache.get(f"o{i}")
    snap = dict(metrics=metrics.snapshot(), store=store.meter.snapshot(),
                consumers=store.consumer_snapshot(),
                events=events.snapshot(), spans=tracer.to_dicts())
    schema = json.loads(
        (pathlib.Path(__file__).parent / "schemas" / "obs.json").read_text())
    errs = validate(json.loads(json.dumps(snap)), schema)
    assert errs == [], errs


def test_fleet_snapshot_validates_against_checked_in_schema():
    import pathlib

    from repro.fleet import Fleet
    store = ObjectStore("s3_internet")
    for i in range(8):
        store.put(f"o{i}", bytes(1500))
    fleet = Fleet(store=store, n_nodes=3, capacity_bytes=4500,
                  window_span=8.0, max_skew=2.0, gossip_every=4)
    for t in range(60):
        fleet.access(f"o{t % 8}", event_time=float(t))
    fleet.flush()
    snap = json.loads(json.dumps(fleet.snapshot()))
    schemas = pathlib.Path(__file__).parent / "schemas"
    errs = validate(snap, json.loads((schemas / "fleet.json").read_text()))
    assert errs == [], errs
    # the obs governance snapshot carries the same shape under "fleet"
    obs_schema = json.loads((schemas / "obs.json").read_text())
    errs = validate(snap, obs_schema["properties"]["fleet"])
    assert errs == [], errs


# ---------------------------------------------------------------------------
# acceptance: full governed ServeEngine run, spans sum to the meter


def test_governed_serve_span_dollars_equal_meter():
    import jax
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.serve import Request, ServeEngine

    tracer, events = Tracer(), EventLog()
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, prefix_cache_bytes=1 << 22,
                         govern=True, governor_window=4,
                         tracer=tracer, events=events)
    rng = np.random.default_rng(5)
    hot = [rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
           for _ in range(2)]
    rid = 0
    for _ in range(4):
        engine.serve([Request(rid + i, h, 2) for i, h in enumerate(hot)])
        rid += len(hot)
    meter = engine.cache.meter
    assert meter.dollars > 0
    span_total = tracer.dollars(name="store.get",
                                consumer=engine.cache.consumer)
    assert span_total == pytest.approx(meter.dollars, rel=1e-12)
    assert events.dollars_billed("miss") == meter.dollars
    # serve spans exist and nest: serve.request -> cache.get
    req = tracer.spans(name="serve.request")
    assert req, "no request spans recorded"
    by_id = {s.span_id: s for s in tracer.spans()}
    for s in tracer.spans(name="cache.get"):
        assert by_id[s.parent_id].name in ("serve.request", "serve.batch")
    snap = engine.governance_snapshot()
    assert "events" in snap and "spans" in snap

"""Serve billing path: one GET per unique prefix, hot-swap invariants,
governed engine wiring."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.registry import get_model
from repro.serve import Request, ServeEngine


def _engine(**kw):
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    return ServeEngine(model, params, prefix_cache_bytes=1 << 22, **kw), cfg


def test_one_get_per_unique_prefix():
    """Repeated identical prefixes bill exactly one GET each: the first
    re-serve fetches the stored prefix KV (billed), every later one hits
    the local cache (never re-billed)."""
    engine, cfg = _engine()
    rng = np.random.default_rng(0)
    a = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    b = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    for _ in range(5):
        engine.serve([Request(0, a, 2)])
    for _ in range(3):
        engine.serve([Request(1, b, 2)])
    assert engine.store.meter.gets == 2            # one per unique prefix
    assert engine.cache.meter.gets == 2            # ... attributed to the cache
    assert engine.cache.hits == (4 - 1) + (2 - 1)  # every later touch is a hit
    # serve the hot prefix once more: still no new billing
    engine.serve([Request(2, a, 2)])
    assert engine.store.meter.gets == 2


def test_hot_swap_mid_stream_preserves_contents_and_billing():
    engine, cfg = _engine(policy="gdsf")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
               for _ in range(3)]
    for p in prompts:
        engine.serve([Request(0, p, 2)])
        engine.serve([Request(1, p, 2)])           # warm: 1 GET per prefix
    gets_before = engine.store.meter.gets
    resident = set(engine.cache._machine.resident())
    engine.cache.set_policy("lru")                 # hot-swap mid-stream
    assert set(engine.cache._machine.resident()) == resident     # contents preserved
    out = [engine.serve([Request(2, p, 2)])[0].output for p in prompts]
    assert engine.store.meter.gets == gets_before  # swap never re-bills
    assert all(o is not None for o in out)
    assert engine.cache.policy == "lru"


def test_governed_engine_serves_and_snapshots():
    engine, cfg = _engine(govern=True, governor_window=4)
    rng = np.random.default_rng(2)
    hot = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    for i in range(6):
        engine.serve([Request(i, hot, 2)])
    assert engine.governor is not None
    snap = engine.governance_snapshot()
    assert "governor" in snap and "metrics" in snap
    assert snap["consumers"].keys() == {"serve_prefix_cache"}
    assert snap["store"]["dollars"] == pytest.approx(
        snap["consumers"]["serve_prefix_cache"]["dollars"])
    # the engine published through the registry
    assert engine.metrics.counter("serve.requests") == 6
    assert engine.metrics.counter("egress.serve_prefix_cache.hits") > 0
    # windowed audit over the prefix traffic works end to end
    rep = engine.governor.audit()
    assert rep is not None and rep.dollar_regret >= 0

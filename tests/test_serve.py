"""Serving engine + egress-billed prefix cache."""
import jax
import numpy as np

from repro.configs import get_config
from repro.models.registry import get_model
from repro.serve.engine import Request, ServeEngine


def _engine(policy="gdsf"):
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    return ServeEngine(model, params, prefix_cache_bytes=1 << 22,
                       policy=policy), cfg


def test_serve_batch_produces_tokens():
    engine, cfg = _engine()
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                    max_new_tokens=3) for i in range(4)]
    done = engine.serve(reqs)
    for r in done:
        assert r.output is not None and r.output.shape == (3,)
        assert (0 <= r.output).all() and (r.output < cfg.vocab_size).all()


def test_greedy_decode_deterministic():
    engine, cfg = _engine()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
    a = engine.serve([Request(0, prompt, 4)])[0].output
    b = engine.serve([Request(1, prompt.copy(), 4)])[0].output
    np.testing.assert_array_equal(a, b)


def test_prefix_cache_reduces_billing():
    engine, cfg = _engine()
    rng = np.random.default_rng(2)
    hot = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    # first serve stores the prefix; repeats hit the local egress cache
    for i in range(5):
        engine.serve([Request(i, hot, 2)])
    rep = engine.audit()
    assert rep.requests >= 4        # prefix touched on every repeat
    assert rep.hit_rate > 0.5
    assert rep.observed_dollars >= 0


def test_mixed_lengths_batched_by_length():
    engine, cfg = _engine()
    rng = np.random.default_rng(3)
    reqs = [Request(0, rng.integers(0, cfg.vocab_size, 8).astype(np.int32), 2),
            Request(1, rng.integers(0, cfg.vocab_size, 16).astype(np.int32), 2),
            Request(2, rng.integers(0, cfg.vocab_size, 8).astype(np.int32), 2)]
    done = engine.serve(reqs)
    assert all(r.output is not None for r in done)

def test_fleet_mode_partitions_prefix_cache():
    """fleet_nodes>0 shards the prefix cache across hash-partitioned
    hosts with their own meters; the engine's audit becomes per-host and
    the governance snapshot carries the fleet state."""
    import math

    cfg = get_config("phi4-mini-3.8b", smoke=True)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, prefix_cache_bytes=1 << 22,
                         policy="lru", fleet_nodes=3, governor_window=4)
    rng = np.random.default_rng(7)
    hot = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
           for _ in range(3)]
    rid = 0
    for _ in range(4):
        engine.serve([Request(rid + i, h, 2) for i, h in enumerate(hot)])
        rid += len(hot)
    fleet = engine.fleet
    assert engine.cache is None and fleet is not None
    assert sum(n.cache.hits + n.cache.misses for n in fleet.nodes) >= 9
    audits = engine.audit()
    assert set(audits) == {n.host for n in fleet.nodes}
    # realized fleet bill == fsum of per-host audits, bit-for-bit
    observed = math.fsum(a.observed_dollars for a in audits.values()
                         if a is not None)
    assert fleet.dollars() == observed
    snap = engine.governance_snapshot()
    assert snap["fleet"]["n_nodes"] == 3
    assert snap["fleet"]["dollars"] == fleet.dollars()


def test_prefill_and_kv_persist_spans_nest_under_batch():
    """Prefill, KV persistence and decode are sibling spans of the batch,
    in that order; prefill ends when its outputs are ready, decode when
    the tokens are on the host."""
    from repro.obs import Tracer
    from repro.serve.engine import _prefix_key
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    model = get_model(cfg)
    tracer = Tracer()
    engine = ServeEngine(model, model.init(jax.random.key(0)),
                         prefix_cache_bytes=1 << 22, tracer=tracer)
    prompt = np.arange(10, dtype=np.int32)
    engine.serve([Request(0, prompt, max_new_tokens=3)])
    assert engine.store.contains(_prefix_key(prompt))
    batch, = tracer.spans(name="serve.batch")
    prefill, = tracer.spans(name="serve.prefill")
    persist, = tracer.spans(name="serve.kv_persist")
    decode, = tracer.spans(name="serve.decode")
    for sp in (prefill, persist, decode):
        assert sp.parent_id == batch.span_id and sp.cat == "serve"
    assert prefill.t0 + prefill.dur <= persist.t0
    assert persist.t0 + persist.dur <= decode.t0
    assert decode.t0 + decode.dur <= batch.t0 + batch.dur

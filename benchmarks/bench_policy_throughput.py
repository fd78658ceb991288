"""JAX lax.scan policy-replay throughput vs the Python reference, plus the
vmapped sweeps — the TPU-native form of the paper's grids.

Two sweep shapes: the original (price x budget) batch for one policy, and
the full (6 policies x 4 prices x 4 budgets) panel as ONE compiled program
(stacked `PolicyWeights` as a third vmap axis).

Obs additions (DESIGN.md §9): `sweep_jax`'s spans separate compile time
from execute time (cold vs warm), and tracing overhead is measured
at two granularities. The acceptance gate is the governed `ServeEngine`
loop (the acceptance criterion's workload): span tracer + decision event
log enabled must cost < 10% over the untraced engine, and a falsy (no-op)
tracer must cost ~0. The raw `EgressCache` replay is also reported — the
worst-case per-access cost of full-fidelity publishing (every access is
dict lookups + a heap push, so ~µs of spans/events is a large *fraction*
there; it is the absolute ns/access that transfers to real workloads)."""
from __future__ import annotations

import numpy as np

from repro.core import Trace, simulate
from repro.core.policies_jax import (POLICY_WEIGHTS, simulate_jax, sweep_jax)
from repro.egress.cache import EgressCache
from repro.egress.store import ObjectStore
from repro.obs import EventLog, MetricsRegistry, NullTracer, Tracer
from .common import Timing, emit, timed


def _egress_replay(cache: EgressCache, keys: list) -> None:
    get = cache.get
    for k in keys:
        get(k)


def trace_overhead(T: int = 20_000, n_objects: int = 256,
                   obj_bytes: int = 4096, cache_objects: int = 64,
                   seed: int = 0):
    """Per-access cost of the obs publishers on the live egress cache."""
    rng = np.random.default_rng(seed)
    store = ObjectStore("s3_internet")
    for i in range(n_objects):
        store.put(f"o{i}", bytes(obj_bytes))
    keys = [f"o{z % n_objects}" for z in rng.zipf(1.2, T)]
    cap = float(cache_objects * obj_bytes)

    def replay(tracer=None, events=None, consumer="bench"):
        cache = EgressCache(store, cap, "gdsf", consumer=consumer,
                            metrics=MetricsRegistry(), tracer=tracer,
                            events=events)
        return timed(_egress_replay, cache, keys, repeats=3)

    _, dt_off = replay(consumer="bench_off")
    _, dt_null = replay(tracer=NullTracer(), consumer="bench_null")
    _, dt_on = replay(tracer=Tracer(max_spans=T), events=EventLog(T),
                      consumer="bench_on")
    return dt_off, dt_null, dt_on


def serve_trace_overhead(rounds: int = 4, hot_prompts: int = 3,
                         repeats: int = 5):
    """Tracing overhead on a full governed ServeEngine loop — the
    acceptance workload: requests through the egress-billed prefix cache
    with the dollar governor live. One engine per config; a warm-up pass
    absorbs jit compilation, then repeats are INTERLEAVED across configs
    (sequential blocks would fold clock/allocator drift into the
    comparison) and min-per-config is the robust estimator."""
    import time as _time

    import jax
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("gemma3-4b", smoke=True)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
               for _ in range(hot_prompts)]

    def serve_rounds(engine):
        rid = 0
        for _ in range(rounds):
            reqs = [Request(rid + i, p, max_new_tokens=4)
                    for i, p in enumerate(prompts)]
            rid += len(reqs)
            engine.serve(reqs)

    def make(tracer=None, events=None):
        return ServeEngine(model, params, prefix_cache_bytes=1 << 22,
                           policy="gdsf", govern=True, governor_window=8,
                           tracer=tracer, events=events)

    engines = dict(off=make(), null=make(tracer=NullTracer()),
                   on=make(tracer=Tracer(max_spans=100_000),
                           events=EventLog(100_000)))
    samples: dict[str, list[float]] = {k: [] for k in engines}
    for e in engines.values():      # compile + fill the prefix cache
        serve_rounds(e)
    for _ in range(repeats):
        for k, e in engines.items():
            t0 = _time.perf_counter()
            serve_rounds(e)
            samples[k].append(_time.perf_counter() - t0)
    return (Timing(samples["off"]), Timing(samples["null"]),
            Timing(samples["on"]))


def main():
    rng = np.random.default_rng(0)
    T, N, B = 20_000, 500, 64
    ids = rng.integers(0, N, T).astype(np.int32)
    costs = 2.0 ** rng.integers(0, 12, N).astype(np.float64)
    tr = Trace(ids=ids, sizes=np.ones(N))

    _, dt_py = timed(lambda: simulate("gdsf", tr, costs, float(B)), repeats=1)
    _, dt_jax = timed(lambda: simulate_jax("gdsf", ids, costs, B,
                                           num_objects=N), repeats=3)
    emit("policy_python_20k", dt_py, f"req_per_s={T/dt_py:.0f}")
    emit("policy_jax_scan_20k", dt_jax,
         f"req_per_s={T/dt_jax:.0f};speedup_vs_py={dt_py/dt_jax:.2f}x")

    # batched 4 price vectors x 4 budgets in one device program, with the
    # compile/execute split (cold then warm — warm reuses the executable)
    cost_matrix = np.stack([costs * (10 ** k) for k in range(4)])
    budgets = np.array([16, 32, 64, 128])
    cold, warm = Tracer(), Tracer()
    sweep_jax("gdsf", ids, cost_matrix, budgets, num_objects=N, tracer=cold)
    out = sweep_jax("gdsf", ids, cost_matrix, budgets, num_objects=N,
                    tracer=warm)

    def seconds(tracer, *names):
        return sum(sp.dur for sp in tracer.spans() if sp.name in names)

    compile_s = seconds(cold, "replay.lower", "replay.compile")
    execute_s = seconds(cold, "replay.execute")
    warm_execute_s = seconds(warm, "replay.execute")
    cells = out.size
    emit("policy_jax_sweep_16cells", warm_execute_s,
         f"cell_per_s={cells/warm_execute_s:.2f};"
         f"req_per_s={cells*T/warm_execute_s:.0f}")
    emit("policy_jax_sweep_profile", compile_s + execute_s,
         f"compile_s={compile_s:.3f};execute_s={execute_s:.4f};"
         f"warm_compile_s="
         f"{seconds(warm, 'replay.lower', 'replay.compile'):.4f};"
         f"compile_frac={compile_s/(compile_s+execute_s):.3f}")

    # the full policy panel: 6 policies x 4 prices x 4 budgets, ONE program
    policies = list(POLICY_WEIGHTS)
    out3, dt_grid = timed(lambda: sweep_jax(policies, ids, cost_matrix,
                                            budgets, num_objects=N),
                          repeats=1)
    cells = out3.size
    # per-policy sweeps for reference: 6 separate compiled programs
    _, dt_loop = timed(
        lambda: [sweep_jax(p, ids, cost_matrix, budgets, num_objects=N)
                 for p in policies], repeats=1)
    emit("policy_jax_grid_96cells", dt_grid,
         f"cell_per_s={cells/dt_grid:.2f};req_per_s={cells*T/dt_grid:.0f};"
         f"one_program_speedup={dt_loop/dt_grid:.2f}x")

    # obs overhead, acceptance gate: governed ServeEngine loop (<10% on,
    # ~0% with the no-op publisher)
    dt_off, dt_null, dt_on = serve_trace_overhead()
    ov_on = dt_on.min / dt_off.min - 1.0
    ov_null = dt_null.min / dt_off.min - 1.0
    emit("serve_trace_overhead_governed", dt_on,
         f"base_us={dt_off*1e6:.0f};overhead_on={ov_on:.3f};"
         f"overhead_null={ov_null:.3f};ok={ov_on < 0.10 and ov_null < 0.02}")

    # worst case: raw per-access publisher cost on the bare egress cache
    # loop (reported in absolute ns/access — the number that transfers)
    T = 20_000
    dt_off, dt_null, dt_on = trace_overhead(T=T)
    emit("egress_trace_cost_20k", dt_on,
         f"base_ns_per_access={dt_off/T*1e9:.0f};"
         f"traced_add_ns_per_access={(dt_on-dt_off)/T*1e9:.0f};"
         f"null_add_ns_per_access={(dt_null-dt_off)/T*1e9:.0f}")
    return None


if __name__ == "__main__":
    main()

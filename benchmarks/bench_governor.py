"""Online dollar-governor vs every fixed policy on the regime-shift trace.

The canonical governance scenario (DESIGN.md §8): the price vector flips
across s* mid-trace (fee-dominated -> egress-dominated), so no fixed
policy wins both phases — recency (LRU) wins while misses cost ~f, the
cost-aware GDSF wins once the bill is byte-weighted. The governor replays
every request against the metadata-only shadow panel ($0 of extra egress,
asserted via per-consumer meters) and hot-swaps the live policy when a
shadow's windowed dollars undercut the incumbent.

Emits per-policy realized dollars + regret vs the best fixed policy in
hindsight, the governed run's dollars/regret/swaps, and the shadow-panel
zero-egress check; also exports the governed run's metrics registry to
`benchmarks/out/governor_metrics.json`.
"""
from __future__ import annotations

from repro.egress.cache import ONLINE_POLICIES
from repro.obs import MetricsRegistry
from repro.online.scenario import (regime_shift_scenario, run_fixed,
                                   run_governed)
from .common import OUT_DIR, emit, timed


def run_panel(n_phase=5000, seed=0, window=400, hysteresis=0.1):
    sc = regime_shift_scenario(n_phase=n_phase, seed=seed)
    fixed = {p: run_fixed(sc, p) for p in ONLINE_POLICIES}
    metrics = MetricsRegistry()
    governed, gov = run_governed(sc, window=window, hysteresis=hysteresis,
                                 auditor_window=4 * window, metrics=metrics)
    best = min(fixed.values(), key=lambda r: r["dollars"])
    store = gov.cache.store
    per_consumer = store.consumer_snapshot()
    shadow_extra = store.meter.dollars - per_consumer["governed"]["dollars"]
    window_audit = gov.audit()
    return dict(scenario=dict(requests=sc.num_requests, flip_at=sc.flip_at,
                              price_a=sc.price_a.name, price_b=sc.price_b.name,
                              capacity=sc.capacity_bytes),
                fixed=fixed, governed=governed, best_fixed=best,
                shadow_extra_dollars=shadow_extra,
                window_audit_regret=(window_audit.dollar_regret
                                     if window_audit else None),
                metrics=metrics)


def main():
    res, dt = timed(run_panel, repeats=1)
    best = res["best_fixed"]
    for p, r in res["fixed"].items():
        reg = (r["dollars"] - best["dollars"]) / best["dollars"]
        emit(f"governor_fixed_{p}", 0.0,
             f"dollars={r['dollars']:.6f};regret_vs_best={reg:.3f};"
             f"hit_rate={r['hit_rate']:.3f}")
    g = res["governed"]
    greg = (g["dollars"] - best["dollars"]) / best["dollars"]
    emit("governor_governed", dt,
         f"dollars={g['dollars']:.6f};regret_vs_best={greg:.3f};"
         f"best_fixed={best['policy']};swaps={len(g['swaps'])};"
         f"final={g['final_policy']}")
    emit("governor_within_10pct", 0.0, f"ok={greg <= 0.10}")
    emit("governor_shadow_zero_egress", 0.0,
         f"extra_dollars={res['shadow_extra_dollars']:.2e};"
         f"ok={abs(res['shadow_extra_dollars']) < 1e-12}")
    if res["window_audit_regret"] is not None:
        emit("governor_window_audit", 0.0,
             f"regret={res['window_audit_regret']:.3f}")
    res["metrics"].write_json(OUT_DIR / "governor_metrics.json")
    return res


if __name__ == "__main__":
    from . import common
    common.reset_records()
    main()
    common.write_json("governor")

"""Policy audit: sweep (policy x price-vector x budget) on the JAX replay
engine and bracket everything against the exact reference — the paper's
Table-1 workflow as a one-command operational tool. The whole sweep is
published through the obs metrics registry and exported as JSON
(`benchmarks/out/policy_audit_metrics.json`).

    PYTHONPATH=src python examples/policy_audit.py
"""
import pathlib

import numpy as np

from repro.core import (PRICE_VECTORS, exact_opt_uniform, heterogeneity,
                        miss_costs, twemcache_like)
from repro.core.policies_jax import sweep_jax
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import MetricsRegistry

OUT = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "out"


def main():
    enable_compile_cache()
    metrics = MetricsRegistry()
    tr = twemcache_like(n_requests=8000, seed=1)
    # page-cache view: audit the *cost* structure with uniform pages
    budgets = np.array([32, 64, 128, 256])
    names = list(PRICE_VECTORS)
    cost_matrix = np.stack([miss_costs(tr.sizes, PRICE_VECTORS[n])
                            for n in names])

    print("trace: twemcache-like,", tr.num_requests, "requests,",
          tr.num_objects, "objects, mean size",
          f"{tr.access_sizes().mean():.0f} B")
    print(f"\n{'price':16s} {'s*':>8s} {'H':>6s} | dollars by budget "
          f"{budgets.tolist()} (gdsf)")
    gdsf = sweep_jax("gdsf", tr.ids, cost_matrix, budgets,
                     num_objects=tr.num_objects)
    lru = sweep_jax("lru", tr.ids, cost_matrix, budgets,
                    num_objects=tr.num_objects)
    for i, n in enumerate(names):
        pv = PRICE_VECTORS[n]
        H = heterogeneity(tr.ids, cost_matrix[i])
        cells = " ".join(f"{d:9.4f}" for d in gdsf[i])
        print(f"{n:16s} {pv.crossover_bytes:8.0f} {H:6.2f} | {cells}")
        metrics.set_gauge(f"audit.{n}.sstar_bytes", pv.crossover_bytes)
        metrics.set_gauge(f"audit.{n}.heterogeneity", H)
        for k, b in enumerate(budgets):
            metrics.observe(f"audit.{n}.gdsf_dollars", float(gdsf[i][k]),
                            step=int(b))
            metrics.observe(f"audit.{n}.lru_dollars", float(lru[i][k]),
                            step=int(b))

    print("\nexact reference at B=64 (first price vector):")
    opt = exact_opt_uniform(tr.ids, cost_matrix[0], 64)
    print(f"  OPT ${opt.dollars:.4f}  vs gdsf ${gdsf[0][1]:.4f} "
          f"vs lru ${lru[0][1]:.4f}")
    metrics.set_gauge(f"audit.{names[0]}.opt_dollars_B64", opt.dollars)
    path = metrics.write_json(OUT / "policy_audit_metrics.json")
    print(f"\nmetrics registry exported to {path}")


if __name__ == "__main__":
    main()
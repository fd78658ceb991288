"""Quickstart: the paper in five minutes on a laptop.

1. Build a workload (synthetic Zipf trace), price it under real cloud
   billing (eq. 1), and locate the GET-fee/egress crossover s* (eq. 3).
2. Compute the EXACT offline dollar-optimum (interval LP == min-cost flow).
3. Score LRU vs cost-aware GDSF in dollars against it.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (PRICE_VECTORS, Trace, exact_opt_uniform,
                        heterogeneity, miss_costs, regret, simulate,
                        zipf_trace)
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    print("=== cloud-egress caching quickstart ===\n")
    # a page-cache workload: uniform 4 KiB pages, heterogeneous miss costs
    # (same-region vs cross-region objects — cost varies, size doesn't)
    rng = np.random.default_rng(0)
    n_objects, T, B = 200, 8000, 24
    ids = rng.choice(n_objects, size=T,
                     p=(lambda p: p / p.sum())(
                         np.arange(1, n_objects + 1.) ** -0.9)).astype(np.int32)
    costs = np.exp(rng.normal(0, 2.0, n_objects))   # heterogeneous $ / miss
    tr = Trace(ids=ids, sizes=np.ones(n_objects), name="quickstart")

    H = heterogeneity(ids, costs)
    print(f"workload: {T} requests over {n_objects} pages, budget {B} pages")
    print(f"miss-cost heterogeneity H = {H:.2f}\n")

    for pv in PRICE_VECTORS.values():
        print(f"  {pv.name:16s} GET=${pv.get_fee:.2e}  "
              f"egress=${pv.egress_per_byte * 1e9:.3f}/GB  "
              f"crossover s* = {pv.crossover_bytes:,.0f} B")
    print()

    opt = exact_opt_uniform(ids, costs, B)
    print(f"exact offline dollar-optimum: ${opt.dollars:,.2f} "
          f"(no-cache ${opt.total_no_cache:,.2f}, "
          f"{opt.hits} retained reuses)\n")

    for policy in ("lru", "lfu", "gds", "gdsf", "belady", "cost_belady"):
        r = simulate(policy, tr, costs, float(B))
        print(f"  {policy:12s} ${r.dollars:10,.2f}   "
              f"dollar-regret {regret(r.dollars, opt.dollars):6.3f}   "
              f"hit-rate {r.hits / tr.num_requests:.3f}")
    print("\ncost-blind LRU leaves money on the table; GDSF buys most of "
          "it back (paper Fig. 1).")


if __name__ == "__main__":
    main()
"""Benchmark entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the harness contract, and writes
a machine-readable ``benchmarks/out/BENCH_<name>.json`` per module so the
perf trajectory is tracked across PRs:

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run crossover  # one
"""
from __future__ import annotations

import sys

from repro.launch.compile_cache import enable_compile_cache

from . import (bench_cdn, bench_contention, bench_costfoo, bench_crossover,
               bench_exact, bench_fleet, bench_flow_scale, bench_governor,
               bench_heterogeneity, bench_kernels, bench_policy_throughput,
               common)

ALL = {
    "exact": bench_exact.main,                    # §2 integrality/brute force
    "heterogeneity": bench_heterogeneity.main,    # Fig. 1
    "contention": bench_contention.main,          # Fig. 2
    "costfoo": bench_costfoo.main,                # §4 bracket
    "crossover": bench_crossover.main,            # Table 1 / Fig. 3
    "cdn": bench_cdn.main,                        # Fig. 4
    "flow_scale": bench_flow_scale.main,          # §6 scale + parametric sweep
    "policy_throughput": bench_policy_throughput.main,  # JAX replay engine
    "kernels": bench_kernels.main,                # Pallas vs oracle
    "governor": bench_governor.main,              # online governance (§8)
    "fleet": bench_fleet.main,                    # fleet governance (§10)
}


def main() -> None:
    enable_compile_cache()
    names = sys.argv[1:] or list(ALL)
    unknown = [n for n in names if n not in ALL]
    if unknown:
        sys.exit(f"unknown benchmark(s) {unknown}; choose from: "
                 + ", ".join(ALL))
    print("name,us_per_call,derived")
    for n in names:
        common.reset_records()
        ALL[n]()
        common.write_json(n)


if __name__ == "__main__":
    main()

"""Find a serve cell's knee once: the same set-up served at several fixed
rates in one process, each for --seconds, printing latency, queue and
drain per rate (not part of a benchmark run).

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 1,2,3 \
        --seconds 30 --seed 1
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import device, spec   # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    device.enable_compile_cache()
    cell = spec.load_cell(args.workload, ROOT / "BENCHMARK.json")
    device.gate(cell.chips)
    driver = cell.driver()
    state = driver.setup(cell, args.seed, args.seconds, False)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        driver.reschedule(state, rate, args.seconds, args.seed + 1000 * i)
        win = driver.window(state, args.seconds, None)
        print(json.dumps({"rate_per_s": rate, "attempted": win.attempted,
                          "failed": win.failed, **win.end_to_end,
                          **{k: win.counters[k] for k in
                             ("batches", "max_queue", "drain_s",
                              "serve_wall_s")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

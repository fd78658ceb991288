"""Readings that set a cell's correctness limits: the program's number and
the control's, on many seeds in one process (not part of a benchmark run).

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

For each seed: the cell's set-up (a serve cell: weights of that seed; the
grid: that seed's catalog on the already compiled program), a short window,
the check against the plain reference, then the control put in the
program's place on the same outputs: the reference in bfloat16 for the
grid, in fp8 for a served model. Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import device, spec   # noqa: E402


def readings(cell, seeds, seconds, gate=device.gate):
    gate(cell.chips)
    driver = cell.driver()
    state = None
    for seed in seeds:
        if cell.traffic["driver"] == "grid" and state is not None:
            driver.reseed(state, seed)
        else:
            state = driver.setup(cell, seed, seconds, False)
        win = driver.window(state, seconds, None)
        checks = {c["name"]: c["value"] for c in driver.check(state, win)}
        yield {"seed": seed, "attempted": win.attempted,
               "failed": win.failed, "program": checks,
               "control": driver.control(state, win),
               "end_to_end": win.end_to_end}
        if cell.traffic["driver"] != "grid":
            state = None
            gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    device.enable_compile_cache()
    cell = spec.load_cell(args.workload, ROOT / "BENCHMARK.json")
    seeds = [int(x) for x in args.seeds.split(",")]
    for row in readings(cell, seeds, args.seconds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

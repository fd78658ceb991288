"""Run one benchmark cell once on the chip and print its result.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Reads BENCHMARK.json at the root of the checkout, finds the cell's
configuration, traffic, driver and per-layer metric readers by name
(harness/spec.py), sets up, measures for --seconds, checks what the timed
path produced against the configuration's plain reference, and prints one
JSON object as the last line of standard output. Each compared number is
printed beside its limit as the last lines of standard error and under the
result's last key, "checks". With --trace 0 the metrics are the cell's
end-to-end ones; with --trace 1 the per-layer ones, read from a profiler
trace of the window and from the system's own spans and counters.

Exits non-zero with no result when JAX finds no TPU or fewer chips than the
cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import gc         # noqa: E402
import json       # noqa: E402
import pathlib    # noqa: E402
import sys        # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import device, spec   # noqa: E402
from harness.counts import peaks   # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Run:
    """What a per-layer metric reader sees of one traced run."""

    def __init__(self, cell, trace, counters, spans, series, peak_row):
        self.cell = cell
        self.trace = trace          # harness.profile.Reduction or None
        self.counters = counters    # counts the driver kept of the window
        self.spans = spans          # the system's own spans in the window
        self.series = series        # the system's metrics registry snapshot
        self.peaks = peak_row       # the device's row of peaks.json


def run(args, gate=device.gate, cell=None) -> dict:
    """One run of one cell; `gate` finds the chips, `cell` (by default the
    one BENCHMARK.json names) says what to run."""
    cell = cell or spec.load_cell(args.workload, ROOT / "BENCHMARK.json")
    devices = gate(cell.chips)
    peak_row = peaks(devices[0].device_kind) if args.trace else None
    driver = cell.driver()
    state = driver.setup(cell, args.seed, args.seconds, bool(args.trace))
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s:.6f}")
    win = driver.window(state, args.seconds,
                        HERE / ".trace" if args.trace else None)
    dev = device.describe(devices)
    for line in win.notes:
        log(line)
    checks = driver.check(state, win)
    gc.collect()
    result = {"correct": all(c["value"] <= c["limit"] for c in checks)
              and win.failed == 0,
              "attempted": win.attempted, "failed": win.failed}
    if args.trace:
        r = win.trace
        dev.update(busy_s=r.busy_ns / 1e9, window_s=r.window_ns / 1e9)
        ctx = Run(cell, r, win.counters, win.spans, win.series, peak_row)
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": r.device_ops(10),
                               "idle_gaps": r.idle_gaps(10)}
    else:
        values = dict(win.end_to_end, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = dev
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    log(f"compile cache: {device.enable_compile_cache()}")
    try:
        result = run(args)
    except device.NoChip as e:
        log(f"no result: {e}")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Profiler capture and the reduction from a trace to the numbers the
per-layer metrics read.

A TPU trace (`jax.profiler`, read back with `ProfileData`) holds one plane
per chip ('/device:TPU:<i>') with a line 'XLA Modules' (one event per
program run) and a line 'XLA Ops' (one event per instruction run, named by
the instruction's text, including its operand and result types), and host
planes whose lines hold the harness's `TraceAnnotation` spans, all on one
clock. The reduction gives:

- busy: the union of the op intervals on each chip inside the traced
  window, averaged over the chips; idle = window - busy;
- per-op and per-kernel device time by stable name (custom calls with
  `custom_call_target="tpu_custom_call"` are kernels, with the bytes their
  operands and results hold);
- per-program device time and run counts;
- idle gaps inside the window longer than 0.1 ms, each charged to the
  harness span ('bench.*') that covers most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import pathlib

from .counts import call_bytes, parse_instruction, stable_name

WINDOW_SPAN = "bench.window"
_CONTAINERS = ("while", "conditional", "call", "async-start", "async-done")


@dataclasses.dataclass
class Reduction:
    window_ns: float
    busy_ns: float
    chips: int
    ops: dict          # instruction name -> [runs, ns, opcode, result type]
    kernels: dict      # stable kernel name -> {"runs", "ns", "bytes"}
    modules: dict      # program name -> [runs, ns]
    idle_by_span: dict  # harness span name -> idle ns charged to it

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def device_ops(self, n: int = 10) -> list:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:n]
        return [[f"{k} {v[2]} {v[3][:80]}", v[1] / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> list:
        top = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]


class Capture:
    """Profile what runs inside `with`; `path` is the trace file after."""

    def __init__(self, directory: str | os.PathLike):
        self.dir = pathlib.Path(directory)
        self.path: pathlib.Path | None = None

    def __enter__(self):
        import jax
        self.dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.dir))
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._span.__exit__(*exc)
        jax.profiler.stop_trace()
        found = sorted(glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        self.path = pathlib.Path(found[-1]) if found else None
        return False


def _union(intervals, lo, hi) -> tuple[float, list]:
    """Covered length of [lo, hi] and the uncovered gaps, given intervals."""
    covered, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= cur:
            continue
        if s > cur:
            gaps.append((cur, s))
            cur = s
        covered += e - cur
        cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return covered, gaps


def _charge(gap, spans) -> str:
    """The harness span that overlaps the gap most ('none' if none does)."""
    best, best_ns = "none", 0.0
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def reduce_trace(path, min_gap_ns: float = 1e5) -> Reduction:
    """Reduce one `.xplane.pb` file (or a ProfileData) to a Reduction."""
    from jax.profiler import ProfileData
    data = path if not isinstance(path, (str, os.PathLike)) else \
        ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    ops, kernels, modules = {}, {}, {}
    per_chip = []
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    m = modules.setdefault(ev.name.split("(")[0], [0, 0.0])
                    m[0] += 1
                    m[1] += ev.duration_ns
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                intervals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                inst = parse_instruction(ev.name)
                if inst["opcode"] in _CONTAINERS:
                    continue
                o = ops.setdefault(inst["name"], [0, 0.0, inst["opcode"],
                                                  inst["result"]])
                o[0] += 1
                o[1] += ev.duration_ns
                if 'custom_call_target="tpu_custom_call"' in inst["attrs"]:
                    k = kernels.setdefault(stable_name(inst["name"]),
                                           {"runs": 0, "ns": 0.0, "bytes": 0.0})
                    k["runs"] += 1
                    k["ns"] += ev.duration_ns
                    k["bytes"] += call_bytes(ev.name)
        per_chip.append(intervals)
    if windows:
        lo, hi = windows[0]
    else:
        flat = [x for iv in per_chip for x in iv]
        lo, hi = min(s for s, _ in flat), max(e for _, e in flat)
    busy, idle_by = 0.0, {}
    inner = [h for h in host if h[0] != WINDOW_SPAN]
    for intervals in per_chip:
        covered, holes = _union(intervals, lo, hi)
        busy += covered
        for g in holes:
            if g[1] - g[0] < min_gap_ns:
                continue
            who = _charge(g, inner)
            idle_by[who] = idle_by.get(who, 0.0) + (g[1] - g[0])
    n = max(1, len(per_chip))
    return Reduction(window_ns=float(hi - lo), busy_ns=busy / n, chips=n,
                     ops=ops, kernels=kernels, modules=modules,
                     idle_by_span={k: v / n for k, v in idle_by.items()})

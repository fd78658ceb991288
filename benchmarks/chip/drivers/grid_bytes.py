"""Byte-budget replay-grid driver: whole (policy x price x byte budget)
grid answers of the system's `sweep_jax` with `budget_unit="bytes"`, one
after another, each a cold-cache replay of the next operationcount
requests of the seeded CDN stream (harness/cdn_gen.py), until the
window's seconds have passed. A traced run traces the window's first
answer and stops there.

End to end: grid_cellreq_per_s = cells x requests x answers over the
elapsed time of all whole answers of the window. Counted: the traced
answer's scan steps and the eviction loop's trips, which the program
records on its `replay.execute` span. Checked as drivers/grid.py checks:
the dollars of every cell of check_answers answers of the window, drawn
from the seed, against the configuration's plain reference
(refs/replay_grid_bytes.py), by the worst relative gap; a policy that the
configuration's limits name has a number of its own.
"""
from __future__ import annotations

import pathlib
import time

import numpy as np

from harness import cdn_gen, spec, stats
from harness.window import CompileCounter, Window, traced

# the uniform grid's check, control and re-seeding read only what State
# holds below
_grid = spec.load_module(pathlib.Path(__file__).with_name("grid.py"),
                         "chipbench_driver_grid")
check, control = _grid.check, _grid.control


class State:
    pass


def _catalog(s, seed: int) -> None:
    """The seed's keys and sizes, every cell's miss costs, and the byte
    budgets (shares of the catalog's bytes, the same for every seed)."""
    c, tr = s.cell.config, s.cell.traffic
    s.seed = seed
    s.keys = cdn_gen.Stream(c, tr, seed)
    s.sizes = s.keys.sizes
    s.costs = np.stack([p["get_fee"] + s.sizes * p["egress_per_byte"]
                        for p in c["grid"]["price_vectors"].values()])
    s.budgets = np.round(s.keys.catalog_bytes * np.asarray(
        c["grid"]["cache_share_of_bytes"])).astype(np.int64)
    s.wanted = {}


def setup(cell, seed: int, seconds: float, tracing: bool):
    import jax
    from repro.core.policies_jax import sweep_jax
    from repro.obs import Tracer
    s = State()
    s.cell = cell
    _catalog(s, seed)
    s.policies = list(cell.config["grid"]["policies"])
    s.tracer = Tracer()

    def answer(ids):
        with jax.profiler.TraceAnnotation("bench.answer"):
            return sweep_jax(s.policies, ids, s.costs, s.budgets,
                             num_objects=cell.config["objectcount"],
                             sizes=s.sizes, tracer=s.tracer,
                             budget_unit="bytes")
    s.answer = answer
    # warm-up: the one shape the window uses, compiled (or loaded) and run
    jax.block_until_ready(s.answer(s.keys.segment(cell.traffic, seed, -1)))
    s.compiles = CompileCounter()
    return s


def window(s, seconds: float, trace_dir) -> Window:
    tr = s.cell.traffic
    T = tr["operationcount"]
    cells = len(s.policies) * len(s.costs) * len(s.budgets)
    answers, red = [], None
    s.compiles.active = True
    t0 = time.perf_counter()
    k = 0
    while True:
        ids = s.keys.segment(tr, s.seed, k)
        if trace_dir is not None:
            out, red = traced(trace_dir, lambda: s.answer(ids))
        else:
            out = s.answer(ids)
        answers.append((k, np.asarray(out)))
        k += 1
        if trace_dir is not None or time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    s.compiles.active = False
    trips = s.tracer.spans(name="replay.execute")[-1].attrs["evict_trips"]
    work = cells * T * len(answers)
    return Window(
        end_to_end={"grid_cellreq_per_s": stats.rate(work, elapsed)},
        attempted=len(answers), failed=0,
        counters={"scan_steps_traced": T if red is not None else 0,
                  "evict_trips_traced": trips if red is not None else 0,
                  "answers": len(answers), "cells": cells,
                  "compiles_in_window": s.compiles.count},
        trace=red, outputs=answers,
        notes=[f"answers={len(answers)} cells={cells} requests={T} "
               f"elapsed_s={elapsed!r} compiles_in_window={s.compiles.count} "
               f"last_answer_evict_trips={trips}"])


def reseed(s, seed: int) -> None:
    """Another seed's catalog and stream on the same compiled program."""
    _catalog(s, seed)

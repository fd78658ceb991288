"""Replay-grid driver: whole (policy x price x cache size) grid answers of
the system's `sweep_jax`, one after another, each a cold-cache replay of the
next operationcount reads of the seeded stream, until the window's seconds
have passed. A traced run traces the window's first answer and stops there.

End to end: grid_cellreq_per_s = cells x reads x answers over the elapsed
time of all whole answers of the window. Checked: the dollars of every cell
of check_answers answers of the window, drawn from the seed, against the
configuration's plain reference (refs/replay_grid.py), by the worst relative
gap; a policy that the configuration's limits name has a number of its own.
"""
from __future__ import annotations

import time

import numpy as np

from harness import gen, stats
from harness.seeds import rng
from harness.window import CompileCounter, Window, traced


class State:
    pass


def _catalog(s, seed: int) -> None:
    """The seed's record keys by rank, and every cell's miss costs."""
    c, tr = s.cell.config, s.cell.traffic
    s.seed = seed
    s.keys = gen.Keys(c, tr, seed)
    s.sizes = gen.record_sizes(c)
    s.costs = np.stack([p["get_fee"] + s.sizes * p["egress_per_byte"]
                        for p in c["grid"]["price_vectors"].values()])
    s.wanted = {}


def setup(cell, seed: int, seconds: float, tracing: bool):
    import jax
    from repro.core.policies_jax import sweep_jax
    c = cell.config
    s = State()
    s.cell = cell
    _catalog(s, seed)
    s.policies = list(c["grid"]["policies"])
    s.budgets = np.asarray([round(f * c["recordcount"]) for f in
                            c["grid"]["cache_share_of_records"]], np.int64)

    def answer(ids):
        with jax.profiler.TraceAnnotation("bench.answer"):
            return sweep_jax(s.policies, ids, s.costs, s.budgets,
                             num_objects=c["recordcount"], sizes=s.sizes)
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
    work = cells * T * len(answers)
    return Window(
        end_to_end={"grid_cellreq_per_s": stats.rate(work, elapsed)},
        attempted=len(answers), failed=0,
        counters={"scan_steps_traced": T if red is not None else 0,
                  "answers": len(answers), "cells": cells,
                  "compiles_in_window": s.compiles.count},
        trace=red, outputs=answers,
        notes=[f"answers={len(answers)} cells={cells} reads={T} "
               f"elapsed_s={elapsed!r} compiles_in_window={s.compiles.count}"])


def _checked(s, win: Window) -> list:
    """The answers the check compares, drawn from the seed."""
    n = min(s.cell.traffic["check_answers"], len(win.outputs))
    pick = rng(s.seed, "check").choice(len(win.outputs), n, replace=False)
    return [win.outputs[j] for j in sorted(pick)]


def _wanted(s, k: int, precision: str = "float32") -> np.ndarray:
    """The plain reference's dollars of answer k, computed once."""
    if (k, precision) not in s.wanted:
        ids = s.keys.segment(s.cell.traffic, s.seed, k)
        s.wanted[k, precision] = s.cell.reference().grid(
            ids, s.costs, s.sizes, s.budgets, s.policies, precision=precision)
    return s.wanted[k, precision]


def _gaps(s, got: np.ndarray, want: np.ndarray, limits: dict) -> dict:
    """Worst relative dollar gap of one answer, per compared number: a
    policy with a limit of its own (`dollar_rel_err.<policy>`) is compared
    apart, every other policy's cells under `dollar_rel_err`."""
    gap = np.abs(got - want) / want
    out = {}
    for q, pol in enumerate(s.policies):
        name = f"dollar_rel_err.{pol}"
        name = name if name in limits else "dollar_rel_err"
        out[name] = max(out.get(name, 0.0), float(np.max(gap[q])))
    return out


def _worst(s, win: Window, control: bool = False) -> dict:
    """Each compared number's worst over the checked answers: of the
    program's dollars, or of the bfloat16 control's in their place."""
    limits = s.cell.config["limits"]
    worst = {}
    for k, got in _checked(s, win):
        if control:
            got = _wanted(s, k, "bfloat16")
        for name, v in _gaps(s, got, _wanted(s, k), limits).items():
            worst[name] = max(worst.get(name, 0.0), v)
    return worst


def check(s, win: Window) -> list[dict]:
    limits = s.cell.config["limits"]
    return [{"name": n, "value": v, "limit": limits[n]}
            for n, v in _worst(s, win).items()] + [
        {"name": "window_compiles", "value": win.counters["compiles_in_window"],
         "limit": 0}]


def control(s, win: Window) -> dict:
    """The bfloat16 control's readings on the answers the check compares."""
    return _worst(s, win, control=True)


def reseed(s, seed: int) -> None:
    """Another seed's records and stream on the same compiled program."""
    _catalog(s, seed)

"""Device time per named scope of the replay grid's scan step.

The program runs each scan step in three `jax.named_scope`s (replay.score,
replay.victim, replay.update). A profiler op event is named by its compiled
instruction and carries no scope, so the map from instruction to scope
comes from the program: `repro.core.policies_jax.step_scopes()`, read from
the text of the grid program it compiled last, which is the one the traced
answer ran. A program without that function gives no number.
"""
from __future__ import annotations


def ms_per_step(run, scope: str):
    """Device time of the ops that `scope` covers in the traced answer, over
    its scan steps, in ms; None where there is no map or no such op ran."""
    from repro.core import policies_jax
    step_scopes = getattr(policies_jax, "step_scopes", None)
    steps = run.counters.get("scan_steps_traced", 0)
    if step_scopes is None or not steps:
        return None
    ns = sum(run.trace.ops[name][1] for name in step_scopes().get(scope, ())
             if name in run.trace.ops)
    return ns / steps / 1e6 if ns else None

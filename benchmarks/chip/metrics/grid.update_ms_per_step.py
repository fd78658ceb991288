"""Device time of the scan step's state update per replay scan step: the
durations of the instructions that the program maps to its `replay.update`
scope (eviction, GreedyDual aging, the insert, and the writes of the
touched object's score, next use and touch time), in the trace of one
whole answer, over its scan steps."""
from harness.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "replay.update")

"""Device time of the scan step's score pass per replay scan step: the
durations of the instructions that the program maps to its `replay.score`
scope (frequency count, hit test and bill, the mask, and every object's
total score), in the trace of one whole answer, over its scan steps."""
from harness.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "replay.score")

"""Device time of the scan step's victim selection per replay scan step:
the durations of the instructions that the program maps to its
`replay.victim` scope (the Mosaic kernel or the jnp reduction, with the
relayouts of the kernel's operands into (rows, 128) tiles), in the trace of
one whole answer, over that answer's scan steps."""
from harness.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "replay.victim")

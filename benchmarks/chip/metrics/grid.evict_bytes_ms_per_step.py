"""Device time of the byte-budget eviction loop per replay scan step: the
durations of the instructions that the program maps to its
`replay.evict_bytes` scope (each trip's victim selection, the Mosaic
kernel or the jnp reduction, the victim's clearing and its bytes, and the
loop's test), in the trace of one whole answer, over its scan steps.
Nothing where the program has no such scope."""
from harness.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "replay.evict_bytes")

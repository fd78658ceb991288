"""Device time of the victim-selection kernel per replay scan step: the
durations of its custom-call events in the trace of one whole answer, over
that answer's scan steps. Nothing when the kernel is not on the path."""


def read(run):
    steps = run.counters.get("scan_steps_traced", 0)
    ns = sum(k["ns"] for name, k in run.trace.kernels.items()
             if "evict" in name)
    return ns / steps / 1e6 if steps and ns else None

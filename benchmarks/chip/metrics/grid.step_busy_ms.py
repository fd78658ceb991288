"""Device busy time per replay scan step: the union of the device's op
intervals over one traced answer, over its scan steps."""


def read(run):
    steps = run.counters.get("scan_steps_traced", 0)
    return run.trace.busy_ns / steps / 1e6 if steps else None

"""Device time of the jitted decode step per decode step, from the trace:
the runs of the one program that ran exactly once per decode step of the
traced batches, their device time over that count."""


def read(run):
    steps = run.counters.get("decode_steps_traced", 0)
    if not steps:
        return None
    runs = [(ns, name) for name, (n, ns) in run.trace.modules.items()
            if n == steps]
    return max(runs)[0] / steps / 1e6 if runs else None

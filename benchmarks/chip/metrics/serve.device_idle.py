"""Share of the traced part of the serving window in which no operation ran
on the device. The breakdown charges each long gap to the harness span the
host was in (bench.serve, bench.wait, bench.audit)."""


def read(run):
    return 100.0 * run.trace.idle_share

"""Share of the traced grid answer in which no operation ran on the device."""


def read(run):
    return 100.0 * run.trace.idle_share

"""Trips of the byte-budget eviction loop per replay scan step in the
traced answer: the trips the program counts on its `replay.execute` span
(the grid's cells run the loop in lockstep, so a trip serves every cell),
over the answer's scan steps. It tells fewer trips from cheaper ones.
Nothing where the program counts no trips."""


def read(run):
    steps = run.counters.get("scan_steps_traced", 0)
    trips = run.counters.get("evict_trips_traced", 0)
    return trips / steps if steps and trips else None

"""Median device time of one execution of a jitted program, from the
trace's ``XLA Modules`` line: only executions wholly inside the traced
window count.  ``per_counter`` divides by a counter (the K steps one
megastep program runs)."""

import statistics


def read(obs, pattern, scale=1.0, per_counter=None):
    trace = obs.get("trace")
    if trace is None:
        return None
    runs = trace.module_runs(pattern)
    if not runs:
        return None
    per = 1.0
    if per_counter is not None:
        per = obs.get("counters", {}).get(per_counter)
        if not per:
            return None
    return scale * statistics.median(runs) / per

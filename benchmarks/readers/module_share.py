"""Share of the traced window that programs of a name took on the
device: the summed device time of the executions whose name matches
``pattern`` and that lie wholly inside the window (the trace's ``XLA
Modules`` line), over the window, averaged over the chips, times
``scale``.  No such execution gives None."""


def read(obs, pattern, scale=100.0):
    trace = obs.get("trace")
    if trace is None or trace.window_s <= 0 or not trace.devices:
        return None
    runs = trace.module_runs(pattern)
    if not runs:
        return None
    return scale * sum(runs) / (trace.window_s * len(trace.devices))

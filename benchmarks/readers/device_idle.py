"""Share of the traced window in which no operation ran on the device:
1 - union of device-op intervals / window, averaged over the chips."""


def read(obs):
    trace = obs.get("trace")
    if trace is None or trace.window_s <= 0 or trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)

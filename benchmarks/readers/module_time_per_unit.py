"""Device time of the programs of a family by the unit of work their
names carry: ``pattern`` has one group that captures an integer (the
engine's prefills: ``^jit__prefill_b(\\d+)``, the bucket's positions);
the summed device time of the matching executions wholly inside the
traced window, over the summed integers, times ``scale``.  Unlike a
median of the executions it does not depend on which sizes the window
caught.  A program whose name carries no such integer gives None."""

import re


def read(obs, pattern, scale=1.0):
    trace = obs.get("trace")
    if trace is None:
        return None
    rx = re.compile(pattern)
    lo, hi = trace.window
    total_ns, units = 0.0, 0
    for dev in trace.devices:
        for name, a, b in dev.modules:
            found = rx.search(name)
            if found and a >= lo and b <= hi:
                total_ns += b - a
                units += int(found.group(1))
    if not units:
        return None
    return scale * total_ns / 1e9 / units

"""Device time of the operations a kernel's name picks out, per step.

``pattern`` is searched in the operation's own name (the text of an
``XLA Ops`` event before its `` = ``, so an operand that mentions the
kernel does not count); the program gives every Pallas kernel one
(``pl.pallas_call(name="rlt_...")``, which the compiler keeps as the
custom call's instruction name, inside ``jvp_..._`` or
``transpose_jvp_...__`` where a transformation wrapped it).  Only
operations inside executions of the program ``module`` that lie wholly
inside the traced window count; the sum is divided by those executions
and by ``per_counter`` (the K steps one megastep program runs).  A
program whose kernels carry no such name gives None.
"""

import bisect
import re


def read(obs, pattern, module, scale=1.0, per_counter=None):
    trace = obs.get("trace")
    if trace is None:
        return None
    per = 1.0
    if per_counter is not None:
        per = obs.get("counters", {}).get(per_counter)
        if not per:
            return None
    op_rx, mod_rx = re.compile(pattern), re.compile(module)
    lo, hi = trace.window
    runs, total_ns = 0, 0.0
    for dev in trace.devices:
        spans = sorted((a, b) for n, a, b in dev.modules
                       if mod_rx.search(n) and a >= lo and b <= hi)
        if not spans:
            continue
        runs += len(spans)
        starts = [a for a, _ in spans]
        for name, a, b in dev.ops:
            if not op_rx.search(name.split(" = ", 1)[0]):
                continue
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and b <= spans[i][1]:
                total_ns += b - a
    if not runs or not total_ns:
        return None
    return scale * total_ns / 1e9 / (runs * per)

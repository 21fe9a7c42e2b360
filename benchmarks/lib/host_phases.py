"""The program's own phases (``rlt:<layer>/<phase>`` annotations, PR
24) against the device's idle gaps, from one ``.xplane.pb``.

``xplane.load`` keeps only ``bench:`` annotations, so ``idle_gaps``
cannot name a phase yet; until a ``benchmark`` PR widens it, this reads
the same file beside it.  An idle gap belongs to the phase open at its
middle on the host (the innermost, where phases nest).

    python -m benchmarks.lib.host_phases .bench_work/trace/<cell>
"""

from __future__ import annotations

import bisect
import os
from typing import Dict, List, Tuple

from . import xplane

PREFIX = "rlt:"
Annotation = Tuple[str, float, float]            # name, start_ns, end_ns


def load_annotations(path: str, prefix: str = PREFIX) -> List[Annotation]:
    """Host-plane events whose name starts with ``prefix``, by start."""
    from jax.profiler import ProfileData

    out: List[Annotation] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return sorted(out, key=lambda r: r[1])


def phase_at(annotations: List[Annotation], starts: List[float],
             t: float, look_back: int = 16) -> str:
    """The annotation open at ``t`` that started last."""
    i = bisect.bisect_right(starts, t)
    for name, a, b in reversed(annotations[max(0, i - look_back):i]):
        if a <= t <= b:
            return name
    return "unattributed"


def _idle_gaps(trace: xplane.Trace) -> List[xplane.Interval]:
    """The first device's idle intervals inside the traced window."""
    if not trace.devices:
        return []
    dev = trace.devices[0]
    busy = xplane.union(xplane.clip(
        ((a, b) for _, a, b in (dev.ops or dev.modules)), trace.window))
    return xplane.gaps(busy, trace.window)


def idle_by_phase(trace: xplane.Trace, annotations: List[Annotation]
                  ) -> Dict[str, Tuple[int, float]]:
    """``{phase: (gaps, idle seconds)}`` over the first device's idle
    gaps inside the traced window."""
    starts = [a for _, a, _ in annotations]
    out: Dict[str, List[float]] = {}
    for a, b in _idle_gaps(trace):
        name = phase_at(annotations, starts, (a + b) / 2)
        rec = out.setdefault(name, [0, 0.0])
        rec[0] += 1
        rec[1] += (b - a) / 1e9
    return {k: (int(n), s) for k, (n, s) in out.items()}


def idle_overlap(trace: xplane.Trace, annotations: List[Annotation]
                 ) -> Dict[str, float]:
    """``{phase: seconds}``: each idle gap's time split over the phases
    it overlaps (a gap often spans several), so the parts of phases
    that tile the loop sum to the idle time.  Nested annotations each
    count their overlap."""
    idle = _idle_gaps(trace)
    gap_starts = [a for a, _ in idle]
    out: Dict[str, float] = {}
    for name, a, b in annotations:
        i = max(0, bisect.bisect_right(gap_starts, a) - 1)
        while i < len(idle) and idle[i][0] < b:
            lo, hi = max(a, idle[i][0]), min(b, idle[i][1])
            if hi > lo:
                out[name] = out.get(name, 0.0) + (hi - lo) / 1e9
            i += 1
    return out


def host_seconds(trace: xplane.Trace, annotations: List[Annotation]
                 ) -> Dict[str, Tuple[int, float]]:
    """``{phase: (events, seconds)}`` of the annotations inside the
    traced window (clipped to it)."""
    lo, hi = trace.window
    out: Dict[str, List[float]] = {}
    for name, a, b in annotations:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        rec = out.setdefault(name, [0, 0.0])
        rec[0] += 1
        rec[1] += (b - a) / 1e9
    return {k: (int(n), s) for k, (n, s) in out.items()}


def describe(path: str, device_prefix: str = "/device:TPU") -> str:
    trace = xplane.load(path, device_prefix)
    annotations = load_annotations(path)
    host, idle = host_seconds(trace, annotations), idle_by_phase(
        trace, annotations)
    over = idle_overlap(trace, annotations)
    idle_s = trace.window_s - trace.busy_s
    ticks = host.get("rlt:serve/decode_wait", (0, 0.0))[0]
    per = f" ({ticks} decode ticks)" if ticks else ""
    out = [f"window {trace.window_s:.6f}s busy {trace.busy_s:.6f}s "
           f"idle {idle_s:.6f}s{per}",
           f"{'phase':34s} {'events':>7s} {'host_s':>10s} "
           f"{'gaps_mid':>9s} {'idle_mid_s':>10s} {'mid_share':>10s} "
           f"{'overlap_s':>10s}"]
    for name in sorted(set(host) | set(idle),
                       key=lambda n: -idle.get(n, (0, 0.0))[1]):
        n, s = host.get(name, (0, 0.0))
        g, i = idle.get(name, (0, 0.0))
        share = 100.0 * i / idle_s if idle_s > 0 else 0.0
        out.append(f"{name:34s} {n:7d} {s:10.6f} {g:9d} {i:10.6f} "
                   f"{share:9.2f}% {over.get(name, 0.0):10.6f}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    target = sys.argv[1]
    if os.path.isdir(target):
        target = xplane.find_xplane(target) or target
    print(describe(target, sys.argv[2] if len(sys.argv) > 2
                   else "/device:TPU"))

"""From a profiler trace to device busy time, idle gaps and op times.

The arithmetic works on plain ``(start_ns, duration_ns)`` lists and is
tested on hand-made intervals; ``load`` is a thin adapter over
``jax.profiler.ProfileData`` that picks the device planes and lines.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per
execution of a jitted program (``jit_<fn>(<id>)``) and whose line
``XLA Ops`` has one event per HLO operation, control flow (``while``,
``conditional``) enclosing its body's operations.  Host threads are
lines of ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans appear
there under their own name.  All on one clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
WINDOW_ANNOTATION = "bench:window"
ANNOTATION_PREFIX = "bench:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    out = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint sorted intervals covering the same time; nested and
    overlapping events count once."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The idle intervals of ``window``: what ``busy`` (disjoint,
    sorted, clipped) leaves uncovered."""
    out: List[Interval] = []
    cursor = window[0]
    for a, b in busy:
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if window[1] > cursor:
        out.append((cursor, window[1]))
    return out


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float]]:
    """``(name, self_ns)`` per event: its duration less the time its
    nested children cover (a ``while`` does not count its body twice).
    ``events`` are ``(name, start_ns, end_ns)`` on one line."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [e[2] - e[1] for e in events]
    stack: List[int] = []
    for i in order:
        _, a, b = events[i]
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][2]:
            self_ns[stack[-1]] -= b - a
        stack.append(i)
    return [(events[i][0], max(self_ns[i], 0.0)) for i in range(len(events))]


# ---------------------------------------------------------------------------
# the trace, reduced
# ---------------------------------------------------------------------------

@dataclass
class DeviceTrace:
    name: str
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    ops: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DeviceTrace]
    annotations: List[Tuple[str, float, float]]   # host, "bench:*"
    window: Interval

    # -- busy / idle --------------------------------------------------------
    def _busy(self, dev: DeviceTrace) -> List[Interval]:
        src = dev.ops or dev.modules
        return union(clip(((a, b) for _, a, b in src), self.window))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds an operation ran on the device inside the window,
        averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(total(self._busy(d)) for d in self.devices) / (
            1e9 * len(self.devices))

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps of the first device, each named by the
        benchmark-side annotation open at its middle (else
        ``unattributed``) and the program that ran before it."""
        if not self.devices:
            return []
        dev = self.devices[0]
        out = []
        mods = sorted(dev.modules, key=lambda m: m[2])
        ends = [m[2] for m in mods]
        for a, b in gaps(self._busy(dev), self.window):
            mid = (a + b) / 2
            host = [n for n, s, e in self.annotations
                    if s <= mid <= e and n != WINDOW_ANNOTATION]
            i = bisect.bisect_right(ends, a + 1)
            name = (host[-1] if host else "unattributed") + "|after:" + (
                _short(mods[i - 1][0]) if i else "start")
            out.append((name, (b - a) / 1e9))
        merged: Dict[str, List[float]] = {}
        for name, s in out:
            merged.setdefault(name, []).append(s)
        rows = [(f"{n} (n={len(v)}, longest {max(v):.6f}s)", sum(v))
                for n, v in merged.items()]
        return sorted(rows, key=lambda r: -r[1])[:top]

    # -- programs and operations -------------------------------------------
    def module_runs(self, pattern: str) -> List[float]:
        """Durations (s) of the executions of programs whose name
        matches ``pattern`` that lie wholly inside the window."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return [
            (b - a) / 1e9
            for d in self.devices for n, a, b in d.modules
            if rx.search(n) and a >= lo and b <= hi
        ]

    def op_runs(self, pattern: str) -> List[float]:
        """Durations (s) of device operations whose name matches."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return [
            (b - a) / 1e9
            for d in self.devices for n, a, b in d.ops
            if rx.search(n) and a >= lo and b <= hi
        ]

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """Device operations by summed self time inside the window
        (first device), numbered instances folded into one name."""
        if not self.devices:
            return []
        dev = self.devices[0]
        lo, hi = self.window
        inside = [(n, a, b) for n, a, b in (dev.ops or dev.modules)
                  if a >= lo and b <= hi]
        sums: Dict[str, float] = {}
        for name, ns in self_times(inside):
            key = _short(name)
            sums[key] = sums.get(key, 0.0) + ns / 1e9
        return sorted(sums.items(), key=lambda r: -r[1])[:top]


def _short(name: str) -> str:
    """``%fusion.123 = ...`` / ``fusion.123`` / ``jit_f(456)`` -> a
    name stable across runs."""
    kernel = " custom-call(" in name
    name = name.split(" = ")[0].lstrip("%")
    name = re.sub(r"\(\d+\)$", "", name)
    name = re.sub(r"[.\-_]\d+$", "", name)[:80]
    return name + "[custom-call]" if kernel else name


# ---------------------------------------------------------------------------
# the adapter
# ---------------------------------------------------------------------------

def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, device_prefix: str = "/device:TPU") -> Trace:
    """Read an ``.xplane.pb``.  Device planes are those named
    ``device_prefix*``.  With ``device_prefix="host-xla"`` (a CPU
    rehearsal has no device plane) the XLA CPU client's own operation
    events stand in, so that the reduction can be exercised; nothing
    read that way is a device number."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    annotations: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            pseudo = DeviceTrace("host-xla")
            for line in plane.lines:
                for ev in line.events:
                    a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        annotations.append((ev.name, a, b))
                    elif device_prefix == "host-xla":
                        stats = dict(ev.stats)
                        if "hlo_module" in stats:
                            pseudo.ops.append((ev.name, a, b))
            if pseudo.ops:
                devices.append(pseudo)
        elif plane.name.startswith(device_prefix):
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    dev.modules = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
                elif line.name == OP_LINE:
                    dev.ops = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            if dev.modules or dev.ops:
                devices.append(dev)
    marks = [(a, b) for n, a, b in annotations if n == WINDOW_ANNOTATION]
    if marks:
        window = (min(a for a, _ in marks), max(b for _, b in marks))
    else:
        spans = [(a, b) for d in devices
                 for _, a, b in (d.ops or d.modules)]
        window = ((min(a for a, _ in spans), max(b for _, b in spans))
                  if spans else (0.0, 0.0))
    return Trace(devices, annotations, window)


def describe(path: str, top: int = 25) -> str:
    """A trace by hand: planes, lines, and on each line the event names
    that took most time, with one event's stats."""
    from jax.profiler import ProfileData

    out: List[str] = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            lo = min(e.start_ns for e in events)
            hi = max(e.start_ns + e.duration_ns for e in events)
            out.append(f"  LINE {line.name!r} events={len(events)} "
                       f"span_s={(hi - lo) / 1e9:.6f} first_ns={lo:.0f}")
            sums: Dict[str, List[float]] = {}
            sample = {}
            for e in events:
                rec = sums.setdefault(e.name, [0, 0.0])
                rec[0] += 1
                rec[1] += e.duration_ns
                sample.setdefault(e.name, e)
            ranked = sorted(sums.items(), key=lambda r: -r[1][1])[:top]
            for name, (n, ns) in ranked:
                stats = {k: str(v)[:120] for k, v in sample[name].stats}
                out.append(f"    {ns / 1e9:10.6f}s n={n:<6d} {name[:160]!r} "
                           f"stats={stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_xplane(target) or target
    print(describe(target))

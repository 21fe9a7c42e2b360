"""Percentiles and inter-token gaps from timestamps."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def inter_token_gaps(arrivals: Iterable[Sequence[float]],
                     window: Optional[Tuple[float, float]] = None
                     ) -> List[float]:
    """Gaps between consecutive token arrivals of each request (the
    first token's wait is TTFT, not a gap).  With ``window``, only the
    gaps whose later arrival lies inside it."""
    gaps: List[float] = []
    for times in arrivals:
        gaps.extend(
            b - a for a, b in zip(times, times[1:])
            if window is None or window[0] <= b <= window[1])
    return gaps


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile."""
    return int(n * (100.0 - q) / 100.0)

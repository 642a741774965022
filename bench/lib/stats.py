"""Percentiles and window arithmetic on the host clock.

Every tail is a percentile over one sample per event (one gap between two
tokens, one request's first token), with linear
interpolation between order statistics (numpy's default method).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) of ``values``, interpolating
    linearly between the two nearest order statistics."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def token_gaps(times: Dict[int, List[float]],
               window: Tuple[float, float]) -> List[float]:
    """Every gap between consecutive tokens of one request, both tokens
    inside ``window``: one sample per gap, in seconds."""
    gaps = []
    for ts in times.values():
        for a, b in zip(ts, ts[1:]):
            if window[0] <= a and b <= window[1]:
                gaps.append(b - a)
    return gaps


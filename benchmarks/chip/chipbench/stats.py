"""Exact percentiles over every sample, and the quartile spread."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """The ``p``-th percentile of every value, linear between the two
    nearest order statistics (numpy's default rule); no buckets."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * p / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)``'s quartiles."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2

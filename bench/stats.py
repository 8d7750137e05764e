"""Percentiles with misses, and the spread of a set of runs."""
from __future__ import annotations

import math
import statistics


def percentile(values: list, q: float, misses: int = 0):
    """Nearest-rank ``q`` percentile of ``values`` plus ``misses`` samples
    that never completed, which rank above every completed one.  ``None``
    when the rank falls on a miss (the caller decides what a miss reads)."""
    n = len(values) + misses
    if n == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))
    vals = sorted(values)
    return vals[rank - 1] if rank <= len(vals) else None


def spread(values: list) -> float:
    """Interquartile distance over the median, as ``statistics.quantiles``
    gives the quartiles."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

"""Percentiles and spreads, written out so that every PR computes them the
same way."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """q in [0, 100], linear interpolation between closest ranks (numpy's
    default).  Raises on an empty sample: a tail of nothing is no number."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return statistics.median(float(v) for v in values)


def iqr_spread(values) -> float:
    """Distance between the first and third quartile, as
    ``statistics.quantiles(values, n=4)`` gives them, as a share of the
    median — the spread the bounds are set from."""
    xs = [float(v) for v in values]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / abs(statistics.median(xs))

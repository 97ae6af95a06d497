"""Order statistics the benchmark reports.

``percentile`` is linear interpolation between order statistics (numpy's
default method), written out so that the benchmark's arithmetic does not
depend on the program under test.
"""

from __future__ import annotations

import math
import statistics


def percentile(vals, p: float) -> float:
    """The p-th percentile (0..100) of ``vals``; raises on an empty sample."""
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile must be in [0, 100], got {p}")
    s = sorted(float(v) for v in vals)
    if not s:
        raise ValueError("percentile of an empty sample")
    rank = (p / 100.0) * (len(s) - 1)
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(s) - 1)
    frac = rank - lo
    return s[lo] * (1.0 - frac) + s[hi] * frac


def spread(vals) -> float:
    """Inter-quartile distance as a share of the median, with the quartiles
    of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles([float(v) for v in vals], n=4)
    return (q3 - q1) / med

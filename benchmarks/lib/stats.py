"""Percentiles, and the rule for how high a percentile a sample supports."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default).  Raises on an empty sample: a
    metric of nothing is left out, not reported as 0."""
    if not len(values):
        raise ValueError("percentile of an empty sample")
    xs = sorted(float(v) for v in values)
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def highest_supported_percentile(n: int, beyond: int = 10,
                                 ladder: Sequence[float] = (50, 90, 95, 99,
                                                            99.9)
                                 ) -> Optional[float]:
    """The highest percentile of ``ladder`` that has at least ``beyond``
    samples beyond it in a sample of ``n`` (choosing-metrics section 1: "the
    highest percentile that has at least ten samples beyond it").  None
    when not even the lowest rung has."""
    best = None
    for q in ladder:
        if round(n * (100.0 - q) / 100.0, 9) >= beyond:
            best = q
    return best


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median: the run-to-run
    spread as the driver computes it."""
    return (percentile(values, 75) - percentile(values, 25)) / median(values)

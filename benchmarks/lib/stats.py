"""Percentiles by one rule: a tail is reported only where the sample bears it.

A q-th percentile needs at least ``BEYOND`` samples above it, so a p90 needs
100 samples and a p95 needs 200; under that the function returns None and
the harness leaves the metric out of the line rather than print a maximum
under a percentile's name.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

BEYOND = 10


def median(xs: Sequence[float]) -> Optional[float]:
    return percentile(xs, 50.0)


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated ``q``-th percentile (0 < q < 100) of ``xs``, or
    None when ``xs`` is empty or, for q > 50, has fewer than ``BEYOND``
    samples beyond it."""
    n = len(xs)
    if n == 0:
        return None
    if q > 50.0 and n * (100.0 - q) / 100.0 < BEYOND:
        return None
    return _at(sorted(xs), q / 100.0)


def _at(s: Sequence[float], q: float) -> float:
    """Linear interpolation at quantile ``q`` of the sorted ``s``."""
    pos = (len(s) - 1) * q
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return float(s[lo] + (s[hi] - s[lo]) * (pos - lo))


def spread(xs: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median: the driver's measure
    of how far runs of the same code disagree."""
    if len(xs) < 2:
        return None
    s = sorted(xs)
    med = _at(s, 0.5)
    return (_at(s, 0.75) - _at(s, 0.25)) / med if med else None

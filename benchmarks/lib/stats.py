"""Percentiles and means as the benchmark takes them everywhere."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    sorted values; raises on an empty list."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def mean(values) -> float:
    v = list(values)
    if not v:
        raise ValueError("mean of nothing")
    return float(sum(v) / len(v))

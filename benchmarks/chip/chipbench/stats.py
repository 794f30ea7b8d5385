"""Percentile arithmetic of the benchmark (the program's
``serve/metrics.py`` percentile, copied so the yardstick stays here)."""
from __future__ import annotations

import math
from typing import Iterable


def percentile(xs: Iterable[float], p: float) -> float:
    """Linear-interpolation percentile (numpy's default method).  A
    missing value is ``math.inf``; it sorts last, so a percentile that
    lands on missing requests reads inf."""
    v = sorted(float(x) for x in xs)
    if not v:
        raise ValueError("percentile of no values")
    pos = (len(v) - 1) * p / 100.0
    lo = math.floor(pos)
    if pos == lo:
        return v[lo]
    return v[lo] + (v[lo + 1] - v[lo]) * (pos - lo)


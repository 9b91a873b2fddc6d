"""Percentiles of a list of samples, by linear interpolation."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """q in [0, 100]. ``inf`` samples (requests never answered) sort
    last, so they count as slower than any answered one."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or math.isinf(xs[hi]):
        return float(xs[hi] if pos > lo else xs[lo])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values) -> float:
    return percentile(values, 50.0)


def summary(values) -> dict:
    xs = [v for v in values if not math.isinf(v)]
    if not xs:
        return {"n": len(values)}
    return {"n": len(values), "median": median(xs), "min": min(xs),
            "max": max(xs), "p95": percentile(xs, 95.0)}

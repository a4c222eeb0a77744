"""Percentile and spread arithmetic, kept with the benchmark so that every
PR computes a number the same way."""

from __future__ import annotations

import math
import statistics
from typing import Dict, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics (numpy's default), on a plain list."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def summary(values: Sequence[float], unit: str = "") -> Dict[str, object]:
    """Median, p95 and the sample count they rest on.  ``p95_supported``
    says whether at least ten samples lie beyond the 95th percentile
    (choosing-metrics, section 1)."""
    n = len(values)
    if n == 0:
        return {"n": 0, "unit": unit}
    return {"n": n, "unit": unit, "p50": percentile(values, 50),
            "p95": percentile(values, 95),
            "p95_supported": n * 0.05 >= 10}


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them
    — the spread the benchmark's bounds are set from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / abs(med) if med else float("inf")

"""Percentiles with their sample counts, and the spread the bounds use."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in [0, 100]."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(float(v) for v in values)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the q-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0 + 1e-9))


def supported(n: int, q: float, beyond: int = 10) -> bool:
    """The choosing-metrics rule: a percentile is reported only with at
    least ``beyond`` samples past it (p95 wants 200, p90 wants 100)."""
    return samples_beyond(n, q) >= beyond


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them:
    the spread the contract's bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

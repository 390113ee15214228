"""What a reader is given, and the reductions readers share."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from benchmark.reduce import stats


@dataclasses.dataclass
class Results:
    """Everything one run produced, for the metric readers."""

    window_s: float
    series: Dict[str, List[float]]        # the benchmark's own samples, ms
    scalars: Dict[str, Any]               # setup_s, match_frames, counts ...
    counters: Dict[str, float]            # the program's exact counts, window
    program_series: Dict[str, List[float]]  # the program's Metrics series
    trace: Any = None                     # reduce.trace.Trace or None
    trace_window: Optional[tuple] = None
    peaks: Optional[Dict[str, Any]] = None
    cost_shapes: Optional[Dict[str, Any]] = None
    values: Dict[str, float] = dataclasses.field(default_factory=dict)


def reduce_samples(samples: List[float], how: str) -> Optional[float]:
    """``p95`` / ``p50`` / ``median`` / ``mean`` / ``sum`` of a sample list,
    or ``share_over_<x>``: the samples above ``x``, in per cent of all;
    nothing where there are no samples."""
    if not samples:
        return None
    if how == "mean":
        return sum(samples) / len(samples)
    if how == "sum":
        return float(sum(samples))
    if how.startswith("share_over_"):
        over = float(how[len("share_over_"):])
        return 100.0 * sum(v > over for v in samples) / len(samples)
    if how == "median":
        how = "p50"
    if how.startswith("p"):
        return stats.percentile(samples, float(how[1:]))
    raise ValueError(f"unknown reduction {how!r}")

"""What every loop kind shares: the comparison record and small helpers."""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Comparison:
    """One number compared by the ``correct`` decision, beside its limit.
    ``ok`` is ``value <= limit``; an exact comparison has the limit 0."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Context:
    """What run.py hands a driver."""

    config: Dict[str, Any]      # benchmark/configs/<config>.json
    traffic: Dict[str, Any]     # benchmark/traffic/<mix>.json
    seed: int
    trace: bool
    control: Optional[str]
    title: Any                  # benchmark.titles.<title>
    reference: Any              # benchmark.reference.<title>_np
    annotate: Any               # name -> context manager on the trace clock


class DriverBase:
    """The part of a loop kind that run.py reads and that no loop kind
    does differently: result containers, and the program's exact counters
    as a difference over the window."""

    def __init__(self, ctx: Context, series: List[str]):
        self.ctx = ctx
        self.series: Dict[str, List[float]] = {name: [] for name in series}
        self.scalars: Dict[str, Any] = {}
        self.attempted = 0
        self.failed = 0
        self.program_metrics = None     # a Metrics sink in the traced run
        self._base: Dict[str, float] = {}
        self._delta: Dict[str, float] = {}

    def _counters(self) -> Dict[str, float]:
        raise NotImplementedError

    def open_counters(self) -> None:
        self._base = self._counters()

    def close_counters(self) -> None:
        self._delta = {k: v - self._base[k]
                       for k, v in self._counters().items()}
        self.scalars.update({f"count.{k}": v
                             for k, v in self._delta.items()})

    def counters(self) -> Dict[str, float]:
        return dict(self._delta)

    def mark_all_failed(self) -> None:
        self.failed = self.attempted

    def cost_shapes(self) -> Dict[str, Any]:
        """Shapes for the bytes functions under benchmark/costs; none by
        default."""
        return {}


def tree_equal(a, b) -> bool:
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def wait_until(due: float) -> None:
    """Sleep to within 1.5 ms of ``due`` (perf_counter seconds), then spin."""
    while True:
        left = due - time.perf_counter()
        if left <= 0:
            return
        if left > 0.0015:
            time.sleep(left - 0.0015)


def limits_of(config: Dict[str, Any]) -> Dict[str, float]:
    """The reference tolerances a configuration states (``limits``)."""
    return {k: float(v["limit"]) for k, v in config["limits"].items()}


def reference_gaps(got_t, got_v, want_t, want_v) -> List[tuple]:
    """Widest absolute gap of translation and of velocity."""
    return [
        ("reference.translation_gap",
         float(np.abs(np.asarray(got_t, np.float64) - want_t).max())),
        ("reference.velocity_gap",
         float(np.abs(np.asarray(got_v, np.float64) - want_v).max())),
    ]

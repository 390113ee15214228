"""From a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

The profiler writes one plane per device (``/device:TPU:0``, with the lines
``XLA Modules`` — one event per executed program — and ``XLA Ops`` — one per
operation) and one for the host (``/host:CPU``, one line per thread, where
``jax.profiler.TraceAnnotation`` spans appear under their own names: the
benchmark's start with ``bench/``, the program's (``obs/trace.py``, one per
layer boundary, nested as the calls nest) with ``ggrs/``). Everything is on
one clock, in nanoseconds.

``load`` turns the file into a plain :class:`Trace`; the functions below
reduce a ``Trace`` and never touch jax, so they are tested on the small
recorded trace in ``benchmark/testdata``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]           # (start_s, end_s)
Block = Tuple[float, float, float]       # (start_s, end_s, busy_s inside)
Span = Tuple[str, float, float]          # (name, start_s, end_s)
SPAN_PREFIXES = ("bench/", "ggrs/")      # the benchmark's, the program's
WINDOW_SPAN = "bench/window"
DROPPED = "Trace Buffers Dropped"
SHORT_GAP_S = 20e-6


@dataclasses.dataclass
class Trace:
    """A trace reduced while it is read (a few seconds of a many-op program
    are millions of events): per device the runs of operations with no gap
    of 20 us or more between them, and how long operations ran inside each
    run; each operation's self time by name; the executed programs; the
    benchmark's and the program's host spans, all together (``spans``, by
    start) and per host thread (``threads``: spans nest within a thread);
    and when the device's trace buffer filled, if it did."""

    spans: List[Span]
    modules: Dict[int, List[Span]]
    blocks: Dict[int, List[Block]]
    op_self_s: Dict[int, Dict[str, float]]
    dropped_at: Optional[float] = None
    threads: List[List[Span]] = dataclasses.field(default_factory=list)


def _device_ordinal(plane_name: str) -> Optional[int]:
    m = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def short_name(hlo_text: str) -> str:
    """``%while.137 = (s32[]{...`` -> ``while.137``: the XLA Ops line names
    an operation by its whole HLO text."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")[:64]


def reduce_ops(events) -> Tuple[List[Block], Dict[str, float]]:
    """Blocks and self times from (name, start_s, end_s) operations sorted
    by start, children after their parent (as the profiler writes them). An
    operation nested in another (the body of a while loop) is keyed
    ``parent/child``; a parent's self time is what its children leave."""
    blocks: List[Block] = []
    self_s: Dict[str, float] = {}
    names: Dict[str, str] = {}
    stack: List[list] = []       # [end, key, self time so far]
    cur_s = cur_e = None
    cur_busy = 0.0
    for name, s, e in events:
        short = names.get(name)
        if short is None:
            short = names[name] = short_name(name)
        while stack and stack[-1][0] <= s:
            _, key, own = stack.pop()
            self_s[key] = self_s.get(key, 0.0) + own
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
            key = stack[0][1] + "/" + short
        else:
            key = short
        if cur_e is None:
            cur_s, cur_e, cur_busy = s, e, e - s
        elif s - cur_e < SHORT_GAP_S:
            if e > cur_e:
                cur_busy += e - max(s, cur_e)
                cur_e = e
        else:
            blocks.append((cur_s, cur_e, cur_busy))
            cur_s, cur_e, cur_busy = s, e, e - s
        stack.append([e, key, e - s])
    while stack:
        _, key, own = stack.pop()
        self_s[key] = self_s.get(key, 0.0) + own
    if cur_e is not None:
        blocks.append((cur_s, cur_e, cur_busy))
    return blocks, self_s


def load(xspace) -> Trace:
    """Reduce a trace with jax's own reader (nothing else needed):
    ``xspace`` is the path of an ``.xplane.pb`` file or the serialized
    XSpace as bytes."""
    from jax.profiler import ProfileData

    data = (ProfileData.from_serialized_xspace(xspace)
            if isinstance(xspace, bytes) else ProfileData.from_file(xspace))

    def timed(line):
        for ev in line.events:
            s = ev.start_ns * 1e-9
            yield ev.name, s, s + ev.duration_ns * 1e-9

    trace = Trace(spans=[], modules={}, blocks={}, op_self_s={})
    for plane in data.planes:
        dev = _device_ordinal(plane.name)
        if dev is not None:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    trace.blocks[dev], trace.op_self_s[dev] = reduce_ops(
                        timed(line))
                elif line.name == "XLA Modules":
                    trace.modules[dev] = list(timed(line))
                elif line.name == "XLA TraceMe":
                    for name, s, _ in timed(line):
                        if name == DROPPED and (trace.dropped_at is None
                                                or s < trace.dropped_at):
                            trace.dropped_at = s
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = [ev for ev in timed(line)
                        if ev[0].startswith(SPAN_PREFIXES)]
                if mine:
                    trace.threads.append(mine)
                    trace.spans.extend(mine)
    trace.spans.sort(key=lambda e: e[1])
    return trace


# -- reductions ---------------------------------------------------------------


def window_of(trace: Trace) -> Interval:
    """The traced window: the ``bench/window`` annotation, cut where the
    device's trace buffer filled and the device's events stop."""
    for name, s, e in trace.spans:
        if name == WINDOW_SPAN:
            if trace.dropped_at is not None:
                e = min(e, max(s, trace.dropped_at))
            return (s, e)
    raise ValueError(f"the trace holds no {WINDOW_SPAN} span")


def _clipped(blocks: List[Block], window: Interval) -> List[Block]:
    lo, hi = window
    out = []
    for s, e, busy in blocks:
        if e <= lo or s >= hi:
            continue
        cs, ce = max(s, lo), min(e, hi)
        share = (ce - cs) / (e - s) if e > s else 1.0
        out.append((cs, ce, busy * share))
    return out


def busy_seconds(trace: Trace, window: Interval) -> float:
    """Seconds of the window in which an operation ran on the device: the
    union of the op intervals, averaged over the devices that ran any."""
    per_device = [sum(b for _, _, b in _clipped(blocks, window))
                  for blocks in trace.blocks.values()]
    per_device = [b for b in per_device if b > 0]
    return sum(per_device) / len(per_device) if per_device else 0.0


def program_time(trace: Trace, pattern: str, window: Interval,
                 within_spans=None) -> Tuple[float, int]:
    """Summed device seconds and the number of executions of the programs
    whose name matches ``pattern`` and that ran wholly inside the window —
    and, with ``within_spans``, started while the host was inside one of
    the spans of those names (which tells the programs of the
    client under test from the far end's where both are anonymous). On
    several devices one execution counts once per device."""
    rx = re.compile(pattern)
    lo, hi = window
    inside = None
    if within_spans:
        inside = sorted((s, e) for name, s, e in trace.spans
                        if name in within_spans)
    total, n = 0.0, 0
    for events in trace.modules.values():
        j = 0
        for name, s, e in events:
            if s < lo or e > hi or not rx.search(name):
                continue
            if inside is not None:
                while j < len(inside) and inside[j][1] <= s:
                    j += 1
                if j >= len(inside) or inside[j][0] > s:
                    continue
            total += e - s
            n += 1
    return total, n


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """The device operations that took most self time over the traced
    stretch, [name, seconds]; ``parent/child`` for an operation inside a
    while loop. Averaged over devices."""
    acc: Dict[str, float] = {}
    for totals in trace.op_self_s.values():
        for key, v in totals.items():
            acc[key] = acc.get(key, 0.0) + v
    devices = max(1, len(trace.op_self_s))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / devices] for k, v in ranked]


def nest(events) -> Tuple[List[tuple], List[tuple]]:
    """From one thread's spans to (instances, segments): per span instance
    ``(name, parent, dur_s, self_s)``, a span's self time being what its
    direct children leave of it; and the thread's timeline cut into disjoint
    ``(start_s, end_s, innermost name or None)`` pieces. THE definition of
    "innermost": of the spans open at an instant, the one that started
    last (of two that start together, the shorter). A child that outlives
    its parent by a rounding of the clock is cut at the parent's end."""
    events = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    instances, segments = [], []
    stack: List[list] = []      # [name, end, dur, self, parent]
    cursor = events[0][1] if events else 0.0

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            name, end, dur, own, parent = stack.pop()
            if end > cursor:
                segments.append((cursor, end, name))
                cursor = end
            instances.append((name, parent, dur, own))

    for name, s, e in events:
        close_until(s)
        if s > cursor:
            segments.append((cursor, s, stack[-1][0] if stack else None))
            cursor = s
        if stack:
            e = min(e, stack[-1][1])
            stack[-1][3] -= e - s
        stack.append([name, e, e - s, e - s,
                      stack[-1][0] if stack else None])
    close_until(float("inf"))
    return instances, segments


def window_thread(trace: Trace) -> List[Span]:
    """The spans of the host thread that drove the window (the one that
    holds ``bench/window``); a hand-made trace has one thread, ``spans``."""
    for spans in trace.threads:
        if any(name == WINDOW_SPAN for name, _, _ in spans):
            return spans
    return trace.spans


def idle_gaps(trace: Trace, window: Interval, n: int = 10) -> List[list]:
    """Idle time of the (first) device inside the window, charged to what
    the host was doing: each gap of 20 us or more between device operations
    goes to the INNERMOST span (``nest``) that the window's thread had open
    at that instant, the program's ``ggrs/`` spans inside the benchmark's
    ``bench/`` ones; what no span covers is ``unattributed``; shorter gaps
    (the device between two operations of one program) are summed as
    ``between_ops_under_20us``. [name, seconds], largest first."""
    if not trace.blocks:
        return []
    blocks = _clipped(trace.blocks[min(trace.blocks)], window)
    lo, hi = window
    acc: Dict[str, float] = {}
    short = sum((e - s) - busy for s, e, busy in blocks)
    if short > 0:
        acc["between_ops_under_20us"] = short
    gaps: List[Interval] = []
    cursor = lo
    for s, e, _ in blocks:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    _, segments = nest([ev for ev in window_thread(trace)
                        if ev[0] != WINDOW_SPAN])
    first = 0
    for a, b in gaps:
        while first < len(segments) and segments[first][1] <= a:
            first += 1
        covered = 0.0
        j = first
        while j < len(segments) and segments[j][0] < b:
            s, e, name = segments[j]
            part = min(b, e) - max(a, s)
            if part > 0 and name is not None:
                acc[name] = acc.get(name, 0.0) + part
                covered += part
            j += 1
        rest = (b - a) - covered
        if rest > 1e-12:
            acc["unattributed"] = acc.get("unattributed", 0.0) + rest
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

"""The one span instrument, and the host-side ring it can record into.

The reference ships only `log`-crate warnings (survey §5: "no spans, no
profiler hooks"). Here every layer boundary opens ONE context manager,
``with self.span("tick_dispatch", frame=f):`` (:class:`Instrumented`),
which reads the clock once at entry and once at exit and feeds three sinks:

1. the ``utils.metrics.Metrics`` series ``<name>_ms`` (Prometheus, the
   benchmark's ``metrics_series`` reader);
2. the :class:`SpanTracer` ring, if the object was given a tracer: nested
   begin/end events exported as Chrome-trace / Perfetto JSON
   (:meth:`SpanTracer.export_perfetto`), a JSONL stream
   (:meth:`SpanTracer.export_jsonl`) and a per-name aggregate
   (:meth:`SpanTracer.summary`); the span also keeps the per-thread stack
   of open span names the sampling profiler reads (``obs/profiler.py``);
3. a ``jax.profiler.TraceAnnotation("ggrs/<name>", **args)``: whenever a
   profiler session is on, the span is in the xplane's host plane on the
   same clock as ``XLA Modules`` / ``XLA Ops``, so an idle gap of the
   device can be put down to what the program was doing
   (``tools/trace_spans.py``).

Design notes:

- A span's parent is the span open on the thread when it starts: spans are
  context managers, so nesting is correct *by construction* in all three
  sinks. Ring export only repairs the two edge cases a bounded ring
  introduces (orphan ends whose begin was evicted, and spans still open at
  export time, which are auto-closed at the final timestamp).
- Off means both sinks null: :meth:`Instrumented.span` hands out the one
  shared :data:`NULL_SPAN` and constructs nothing, so an instrumented hot
  loop pays one method call + context enter/exit per span — guarded under
  2 % of a 500-frame loopback session by ``tests/test_obs.py``.
- Two program events come from outside any ``with``: collector pauses
  (``gc_pause``, a ``gc.callbacks`` hook) and executables obtained
  (``compile``, fed by ``utils.xla_cache``'s listener). Both go to every
  live object that called :func:`attach_process_events`.
"""

from __future__ import annotations

import collections
import gc
import json
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

from jax import named_scope
from jax.profiler import TraceAnnotation

from bevy_ggrs_tpu.utils.metrics import null_metrics

# Every program span's name on the profiler's host plane starts with this
# (the benchmark's own spans start with ``bench/``), and so does every
# device scope's part of an operation's ``op_name`` (:func:`device_scope`).
TRACE_PREFIX = "ggrs/"


def device_scope(name: str):
    """``with device_scope("ring_write"):`` around the lines that TRACE a
    piece of a jitted program: the device-side counterpart of a span.
    Every operation traced inside carries ``ggrs/<name>`` in its
    ``op_name``, nested scopes in order, through the compiler's passes and
    into the optimized module's ``metadata=``; ``utils.xla_cache.
    record_executable_cost`` reads them back as the map from a compiled
    operation's name to its scopes (``executable_phases``), which joined
    with a device trace's operation times is device time by phase. A scope
    is trace-time metadata: the call costs nothing and the computation is
    the unscoped one's (docs/observability.md, "Device scopes")."""
    return named_scope(TRACE_PREFIX + name)

# Event tuples: ("B", name, ts_us, args) / ("E", name, ts_us, None)
#             / ("I", name, ts_us, args)   (instant)


# -- cross-thread span-stack registry -----------------------------------
#
# The sampling profiler (obs/profiler.py) runs on its OWN thread and must
# answer "which obs span is open on the *sampled* thread right now?" — a
# plain threading.local can't be read from outside, so the per-thread
# stacks live in a module dict keyed by thread ident. Mutation is only
# ever by the owning thread (append/pop under the GIL); the sampler takes
# a snapshot with tuple(), which cannot interleave with a list mutation
# in CPython. Entries are tokens rather than bare names so a span that
# closes out of LIFO order (the admission path's ``first_frame`` opens at
# enqueue and closes a later frame, overlapping everything between) is
# removed by identity instead of corrupting its neighbours.

_SPAN_STACKS: Dict[int, List["_StackToken"]] = {}
_STACKS_LOCK = threading.Lock()  # guards registry insertion only


class _StackToken:
    # ``child_ms``: what the spans that closed directly under this one
    # covered so far (a span's self time is its duration less this);
    # None for a bare marker (``push_span`` outside a span), which is
    # nobody's parent: it may overlap its neighbours.
    __slots__ = ("name", "child_ms")

    def __init__(self, name: str, child_ms: Optional[float] = None):
        self.name = name
        self.child_ms = child_ms


def _stack_for(ident: Optional[int] = None) -> List["_StackToken"]:
    ident = threading.get_ident() if ident is None else ident
    stack = _SPAN_STACKS.get(ident)
    if stack is None:
        with _STACKS_LOCK:
            stack = _SPAN_STACKS.setdefault(ident, [])
    return stack


def push_span(name: str, child_ms: Optional[float] = None) -> _StackToken:
    """Mark ``name`` as the innermost open span on the calling thread.
    Returns a token for :func:`pop_span`. A :class:`_Span` passes 0.0 for
    ``child_ms`` and is credited its children's durations there."""
    tok = _StackToken(name, child_ms)
    _stack_for().append(tok)
    return tok


def pop_span(token: _StackToken) -> Optional[_StackToken]:
    """Close a span marker and return its parent's: the innermost SPAN
    still open (bare markers are skipped), or None where there is none.
    Tolerates non-LIFO closes (removal by token identity; no parent is
    named for one) and double-pops (a missing token is a no-op)."""
    stack = _SPAN_STACKS.get(threading.get_ident())
    if not stack:
        return None
    if stack[-1] is token:
        stack.pop()
        for parent in reversed(stack):
            if parent.child_ms is not None:
                return parent
        return None
    try:
        stack.remove(token)
    except ValueError:
        pass
    return None


def open_span_stack(thread_ident: int) -> Tuple[str, ...]:
    """Snapshot of the open-span names on ``thread_ident``, outermost
    first. Safe to call from any thread (this is the profiler's read)."""
    stack = _SPAN_STACKS.get(thread_ident)
    if not stack:
        return ()
    return tuple(tok.name for tok in tuple(stack))


class _Span:
    """One open span: Metrics series + tracer ring + trace annotation.
    Once closed, ``ms`` is its duration and ``self_ms`` what the spans
    that closed directly under it (of any object on the thread) left of
    it, for a site that writes a sum or a remainder as a series."""

    __slots__ = (
        "_metrics", "_tr", "_name", "_args", "_series", "_t0", "_b_us",
        "_tok", "_ann", "ms", "self_ms",
    )

    def __init__(self, metrics, tracer, name: str, args, series: bool = True):
        self._metrics = metrics
        self._tr = tracer
        self._name = name
        self._args = args
        self._series = series
        self.ms = self.self_ms = 0.0

    def __enter__(self):
        name, args = self._name, self._args
        self._ann = ann = TraceAnnotation(TRACE_PREFIX + name, **args)
        ann.__enter__()
        self._tok = push_span(name, 0.0)
        self._t0 = t0 = time.perf_counter()
        if self._tr is not null_tracer:
            self._b_us = self._tr._begin(name, t0, args or None)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ms = ms = (t1 - self._t0) * 1000.0
        if self._series:
            self._metrics.observe(self._name + "_ms", ms)
        if self._tr is not null_tracer:
            self._tr._end(self._name, t1, self._b_us)
        parent = pop_span(self._tok)
        if parent is not None:
            parent.child_ms += ms
        self.self_ms = ms - self._tok.child_ms
        self._ann.__exit__(*exc)
        return False


class _NullSpan:
    __slots__ = ()

    ms = self_ms = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


# Span-name prefix -> (track offset, track name). Export assigns each
# component a stable tid (`tid * 4 + offset`) so a merged trace shows
# session / spec / server / relay as separate named rows per process
# instead of one flat track. Runtime order is globally LIFO (context
# managers), and restricting a well-nested sequence to one component
# keeps it well-nested, so per-(pid,tid) B/E matching holds without
# restructuring the ring.
_COMPONENT_TRACKS = (
    ("spec", 1, "spec"),
    ("serve", 2, "server"),
    ("srv", 2, "server"),
    ("relay", 3, "relay"),
)
_SESSION_TRACK = (0, "session")


def _component_track(name: str):
    head = name.split("_", 1)[0]
    for prefix, offset, track in _COMPONENT_TRACKS:
        if head == prefix:
            return offset, track
    return _SESSION_TRACK


class SpanTracer:
    """Enabled tracer. ``pid`` distinguishes peers when several tracers'
    exports are merged into one trace (each peer is a Perfetto process)."""

    enabled = True

    def __init__(
        self,
        capacity: int = 200_000,
        clock=time.perf_counter,
        pid: int = 0,
        tid: int = 0,
        process_name: Optional[str] = None,
        wall_t0: Optional[float] = None,
    ):
        self._clock = clock
        self._origin = clock()
        self._events = collections.deque(maxlen=int(capacity))
        self._agg: Dict[str, List[float]] = {}  # name -> [count, total, max]
        self._depth = 0
        self.pid = int(pid)
        self.tid = int(tid)
        self.process_name = process_name
        # Wall-clock instant of ts=0, so the merge tool can align traces
        # captured by different processes (virtual-clock tracers share a
        # timeline already; real-clock ones need this anchor).
        self.wall_t0 = time.time() if wall_t0 is None else float(wall_t0)

    def _now_us(self) -> int:
        return int((self._clock() - self._origin) * 1e6)

    def _ts_us(self, t_perf: float) -> int:
        """Ring timestamp of a ``time.perf_counter()`` reading a span
        already took: the reading itself on the default clock, the
        tracer's own clock (a test's virtual network time) otherwise."""
        if self._clock is time.perf_counter:
            return int((t_perf - self._origin) * 1e6)
        return self._now_us()

    def _begin(self, name: str, t_perf: float, args) -> int:
        ts = self._ts_us(t_perf)
        self._events.append(("B", name, ts, args))
        self._depth += 1
        return ts

    def _end(self, name: str, t_perf: float, begin_us: int) -> None:
        end = self._ts_us(t_perf)
        self._events.append(("E", name, end, None))
        self._depth -= 1
        dur = (end - begin_us) / 1000.0
        agg = self._agg.get(name)
        if agg is None:
            self._agg[name] = [1, dur, dur]
        else:
            agg[0] += 1
            agg[1] += dur
            if dur > agg[2]:
                agg[2] = dur

    # -- instruments ----------------------------------------------------

    def span(self, name: str, **args) -> _Span:
        """A span on this ring and on the trace clock, with no series:
        what an object that was given a tracer and no ``Metrics`` opens."""
        return _Span(null_metrics, self, name, args)

    def instant(self, name: str, **args) -> None:
        self._events.append(("I", name, self._now_us(), args or None))

    # -- reporting ------------------------------------------------------

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name {count, total_ms, mean_ms, max_ms}."""
        return {
            name: {
                "count": int(c),
                "total_ms": total,
                "mean_ms": total / c if c else 0.0,
                "max_ms": mx,
            }
            for name, (c, total, mx) in self._agg.items()
        }

    def _well_formed_events(self):
        """Runtime events repaired to a provably matched, nested sequence:
        begins always emit; an end emits only when it matches the top of
        the reconstructed stack (an end whose begin was evicted from the
        ring is dropped); spans still open at export time are closed at
        the final timestamp, innermost first. Timestamps are monotonized
        (the clock already is; this guards a caller-supplied clock)."""
        out = []
        stack: List[str] = []
        last_ts = 0
        # A snapshot: a collection during the walk appends its
        # ``gc_pause`` to the ring of an attached owner.
        for ph, name, ts, args in list(self._events):
            if ts < last_ts:
                ts = last_ts
            last_ts = ts
            if ph == "B":
                stack.append(name)
                out.append(("B", name, ts, args))
            elif ph == "E":
                if stack and stack[-1] == name:
                    stack.pop()
                    out.append(("E", name, ts, None))
                # else: orphan end (begin evicted) — drop
            else:
                out.append(("I", name, ts, args))
        for name in reversed(stack):
            out.append(("E", name, last_ts, None))
        return out

    def export_perfetto(self, path: Optional[str] = None) -> dict:
        """Chrome trace-event JSON (the format Perfetto's legacy importer
        and ``chrome://tracing`` load). Returns the trace dict; also
        writes it to ``path`` when given."""
        events = []
        if self.process_name is not None:
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": self.pid,
                    "tid": self.tid,
                    "args": {"name": self.process_name},
                }
            )
        named_tracks = set()
        body = []
        for ph, name, ts, args in self._well_formed_events():
            offset, track = _component_track(name)
            tid = self.tid * 4 + offset
            if tid not in named_tracks:
                named_tracks.add(tid)
                events.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": self.pid,
                        "tid": tid,
                        "args": {"name": track},
                    }
                )
            ev = {
                "name": name,
                "cat": "ggrs",
                "ph": "i" if ph == "I" else ph,
                "ts": ts,
                "pid": self.pid,
                "tid": tid,
            }
            if ph == "I":
                ev["s"] = "t"  # thread-scoped instant
            if args:
                ev["args"] = dict(args)
            body.append(ev)
        events.extend(body)
        trace = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "wall_t0": self.wall_t0,
                "pid": self.pid,
                "process_name": self.process_name,
            },
        }
        if path is not None:
            with open(path, "w") as f:
                json.dump(trace, f)
        return trace

    def export_jsonl(self, path: str) -> int:
        """One JSON object per line, runtime order; returns lines written."""
        n = 0
        with open(path, "w") as f:
            for ph, name, ts, args in self._well_formed_events():
                rec = {"ph": ph, "name": name, "ts_us": ts}
                if args:
                    rec["args"] = dict(args)
                f.write(json.dumps(rec) + "\n")
                n += 1
        return n


class _NullTracer:
    """Shared no-op tracer: every instrument is O(1) and allocation-free
    (mirrors ``utils.metrics.null_metrics``)."""

    __slots__ = ()

    enabled = False

    def span(self, name: str, **args) -> _NullSpan:
        return NULL_SPAN

    def instant(self, name: str, **args) -> None:
        pass

    def summary(self):
        return {}

    def export_perfetto(self, path: Optional[str] = None) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def export_jsonl(self, path: str) -> int:
        return 0


null_tracer = _NullTracer()


class Instrumented:
    """Mixin of every instrumented object: its two sinks (``metrics=`` and
    ``tracer=`` arguments, either may stay null) and the one
    :meth:`span` over them. The sinks are read at each call, so assigning
    ``obj.tracer = t`` after construction takes effect; an object that
    lends its instrument to a part of itself (the runner to its
    executors) passes its bound ``span``."""

    metrics = null_metrics
    tracer = null_tracer
    # An object with a third listener for its spans' durations (the
    # serving core's rolling ``timeseries``) keeps them on for it.
    _span_always = False

    def _set_sinks(self, metrics=None, tracer=None) -> None:
        self.metrics = metrics if metrics is not None else null_metrics
        self.tracer = tracer if tracer is not None else null_tracer

    def span(self, name: str, *, series: bool = True, **args):
        """``with self.span("tick_dispatch", frame=f):`` — series
        ``<name>_ms``, tracer ring, ``ggrs/<name>`` on the trace clock.
        ``series=False`` for a span whose duration (``.ms`` once closed)
        the site folds into a series of another key: a sum or a self
        time."""
        if (
            self.metrics is null_metrics
            and self.tracer is null_tracer
            and not self._span_always
        ):
            return NULL_SPAN
        return _Span(self.metrics, self.tracer, name, args, series)

    def timed_span(self, name: str, **args):
        """A span that measures with the sinks off too, for a site whose
        duration feeds a counter the object keeps regardless."""
        return _Span(self.metrics, self.tracer, name, args, False)


def null_span(name: str, **args) -> _NullSpan:
    """The ``span`` of a part nobody lent an instrument to."""
    return NULL_SPAN


# -- program events from outside any ``with`` ---------------------------
#
# A collection of the interpreter's garbage collector and an executable
# obtained by jax are not bracketed by program code; each is recorded, as
# a span that already ended, to the sinks of every live object that
# attached itself (a GGRSStage or MatchServer with a real sink). Objects
# are held weakly: one that is dropped without close() stops receiving
# (the hook then idles until the next attach or detach).

_PROCESS_SINKS: "weakref.WeakSet[Instrumented]" = weakref.WeakSet()
_gc_open: Optional[Tuple[float, TraceAnnotation]] = None


def _on_gc(phase: str, info: dict) -> None:
    """Every collection, of any generation, as a span ``gc_pause``."""
    global _gc_open
    if phase == "start":
        if _PROCESS_SINKS:
            ann = TraceAnnotation(
                TRACE_PREFIX + "gc_pause", generation=info["generation"]
            )
            ann.__enter__()
            _gc_open = (time.perf_counter(), ann)
    elif _gc_open is not None:
        t1 = time.perf_counter()
        (t0, ann), _gc_open = _gc_open, None
        ann.__exit__(None, None, None)
        args = {"generation": info["generation"]}
        for obj in tuple(_PROCESS_SINKS):
            obj.metrics.observe("gc_pause_ms", (t1 - t0) * 1000.0)
            tr = obj.tracer
            if tr is not null_tracer:
                tr._end("gc_pause", t1, tr._begin("gc_pause", t0, args))


def attach_process_events(obj: Instrumented) -> bool:
    """Send ``gc_pause`` and ``compile`` events to ``obj``'s sinks from
    now until :func:`detach_process_events` (or until it is dropped).
    Nothing is attached, and False returned, while both sinks are null.
    At most one ``gc.callbacks`` hook exists per process."""
    if obj.metrics is null_metrics and obj.tracer is null_tracer:
        return False
    _PROCESS_SINKS.add(obj)
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    return True


def detach_process_events(obj: Instrumented) -> None:
    _PROCESS_SINKS.discard(obj)
    if not _PROCESS_SINKS and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def record_compile(program: str, ms: float, cache: Optional[str]) -> None:
    """One executable obtained (``utils.xla_cache``'s listener calls this
    when jax reports the duration): series ``compile_ms``, the tracer
    ring, and an instant ``ggrs/compile`` on the trace clock."""
    if not _PROCESS_SINKS:
        return
    args = {"program": program, "cache": cache or "", "ms": round(ms, 3)}
    with TraceAnnotation(TRACE_PREFIX + "compile", **args):
        pass
    for obj in tuple(_PROCESS_SINKS):
        obj.metrics.observe("compile_ms", ms)
        obj.tracer.instant("compile", **args)

"""A jitted call split at the launch: for every instance of the host span
``span`` (the program's span around ONE jitted call: ``ggrs/serve_dispatch``,
``ggrs/tick_enqueue``) inside the traced window, the first device program
(``XLA Modules`` line, optionally only names matching ``pattern``) that
starts at or after the span's start and before the next instance's start
is the program that call launched. Both lines are on one clock, so

- ``launch_lag`` = program start - span start: what the call did before
  the device had the program (argument handling, the launch itself, and
  any wait for a device still busy with an earlier program);
- ``call_tail`` = span end - program start: what the call still did after
  the device had started (output buffers, bookkeeping); negative where the
  call returned before the device started.

"One clock" holds as far as the profiler aligned the session's device
clock with the host's, once a session. Within a session the instances are
tight; between sessions the split moves by tenths of a millisecond, and in
some ``server256.synctest`` / ``server256.wan`` sessions on the v5e
machine the device's line lies 0.3-1.0 ms EARLY, every instance alike
(``PERF.md`` section 5, PR 35: in the earliest, programs start before the
call that launched them begins, and the call's own duration and its
variation are those of a session that reads late). Such a session gives
``launch_lag`` too small and ``call_tail`` too large by its offset: read
the two medians beside the call's own of the same run, over several runs.

The two add up to the span's duration, instance by instance. ``part`` names
which one; ``reduce`` (default ``p50``) how the instances are reduced; in
ms. An instance with no program (the window cut it off, or the call
launched nothing) is left out; nothing where no instance has one: a
program without the span (a parent commit) or an untraced run.
"""

from __future__ import annotations

import bisect
import re
from typing import List, Tuple

from benchmark.readers.common import reduce_samples
from benchmark.reduce import trace as reduce_trace

PARTS = ("launch_lag", "call_tail")
Launch = Tuple[float, float]          # (launch_lag_s, call_tail_s)


def launches(trace, span: str, window, pattern=None) -> List[Launch]:
    """``(launch_lag_s, call_tail_s)`` of every instance of ``span`` on the
    window's thread that lies wholly inside ``window`` and has a program,
    in order of time. The programs are the first device's."""
    if not trace.modules:
        return []
    lo, hi = window
    calls = sorted((s, e) for name, s, e in reduce_trace.window_thread(trace)
                   if name == span and s >= lo and e <= hi)
    rx = re.compile(pattern) if pattern else None
    starts = sorted(s for name, s, _ in trace.modules[min(trace.modules)]
                    if rx is None or rx.search(name))
    out = []
    for k, (s, e) in enumerate(calls):
        before = calls[k + 1][0] if k + 1 < len(calls) else hi
        j = bisect.bisect_left(starts, s)
        if j < len(starts) and starts[j] < before:
            out.append((starts[j] - s, e - starts[j]))
    return out


def read(spec, results):
    if results.trace is None:
        return None
    part = PARTS.index(spec["part"])
    pairs = launches(results.trace, spec["span"], results.trace_window,
                     spec.get("pattern"))
    return reduce_samples([1e3 * p[part] for p in pairs],
                          spec.get("reduce", "p50"))

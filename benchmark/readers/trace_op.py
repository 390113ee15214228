"""Device time of the operations whose name matches ``pattern``, per
execution of the programs that match ``per_program``, in ms: the
operations' self time summed over the traced stretch (the reducer's
``op_self_s``; the last part of a ``parent/child`` key is the operation's
own name), over the count of those programs inside the window
(``within_spans`` as in ``trace_program``). Nothing where the program under
test has no such operation (a parent commit without the scope that names
it) or none of the programs ran."""

import re

from benchmark.reduce import trace as reduce_trace


def read(spec, results):
    trace = results.trace
    if trace is None or not trace.op_self_s:
        return None
    rx = re.compile(spec["pattern"])
    total = sum(seconds for ops in trace.op_self_s.values()
                for key, seconds in ops.items()
                if rx.search(key.rsplit("/", 1)[-1]))
    total /= len(trace.op_self_s)
    _, n = reduce_trace.program_time(
        trace, spec["per_program"], results.trace_window,
        within_spans=spec.get("within_spans"))
    if total <= 0 or n == 0:
        return None
    return 1e3 * total / n

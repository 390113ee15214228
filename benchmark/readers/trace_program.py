"""Device time of the programs whose name matches ``pattern``, from the
profiler's trace: summed device duration over executions, in ms.
``within_spans`` keeps only programs started while the host was inside one
of the benchmark's spans of those names."""

from benchmark.reduce import trace as reduce_trace


def read(spec, results):
    if results.trace is None:
        return None
    total, n = reduce_trace.program_time(
        results.trace, spec["pattern"], results.trace_window,
        within_spans=spec.get("within_spans"))
    if n == 0:
        return None
    return 1e3 * total / n

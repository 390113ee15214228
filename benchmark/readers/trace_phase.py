"""Device time of one PHASE of a program, or of the operations under one
device SCOPE, per execution of the programs that match ``per_program``, in
ms: the trace's operation times joined with the program's own phase map.

A device trace names an operation by its instruction's name in the
optimized module and nothing else; the program says which instruction was
traced under which of its device scopes (``obs/trace.py``
``device_scope``; the map ``utils.xla_cache.executable_phases()`` keeps for
every executable captured under a name that starts with ``program``). An
operation's scopes stand outermost first: its PHASE is the first, and it
lies under every scope named. ``by``:

- ``phase``: the operations whose phase is ``scope``;
- ``scope``: the operations under ``scope``, at any depth;
- ``rest``: the programs' own time an execution (as ``trace_program``) less
  every operation the map gives a phase: what the compiler made without a
  scope, and what the operations' self times leave of the program.

The operations' self time is summed over the traced stretch and the devices
(the reducer's ``op_self_s``; the last part of a ``parent/child`` key is the
operation's own name) and divided by the count of those programs inside the
window, where an execution counts once a device (``reduce.trace.
program_time``; ``within_spans`` as in ``trace_op``). Nothing
where the program keeps no map (a parent commit before the capture did, a
run whose capture was not armed), where the map holds no scope, or where
none of the programs ran: the metric is then left out, never read as 0.
"""

from benchmark.reduce import trace as reduce_trace


def phase_map(program: str) -> dict:
    """``{instruction name: (scope, ...)}`` of the captured executables
    whose name starts with ``program``; empty where there is none."""
    from bevy_ggrs_tpu.utils import xla_cache

    phases = getattr(xla_cache, "executable_phases", None)
    ops = {}
    if phases is not None:
        for name, record in phases().items():
            if name.startswith(program):
                ops.update(record["ops"])
    return ops


def scoped_ops(trace, ops: dict):
    """``(key, seconds, scopes)`` of every operation of ``trace``, each
    device's: THE join of a trace with a phase map. The last part of a
    ``parent/child`` key is the operation's own name; ``()`` for an
    operation the map does not hold."""
    for self_s in trace.op_self_s.values():
        for key, seconds in self_s.items():
            yield key, seconds, ops.get(key.rsplit("/", 1)[-1], ())


def read(spec, results):
    trace = results.trace
    if trace is None or not any(trace.op_self_s.values()):
        return None
    ops = phase_map(spec["program"])
    if not ops:
        return None
    program_s, n = reduce_trace.program_time(
        trace, spec["per_program"], results.trace_window,
        within_spans=spec.get("within_spans"))
    if n == 0:
        return None
    by, scope = spec["by"], spec.get("scope")
    if by == "phase":
        wanted = lambda scopes: scopes[0] == scope       # noqa: E731
    elif by == "scope":
        wanted = lambda scopes: scope in scopes          # noqa: E731
    elif by == "rest":
        wanted = lambda scopes: True                     # noqa: E731
    else:
        raise ValueError(f"trace_phase: unknown 'by' {by!r}")
    total = sum(seconds for _, seconds, scopes in scoped_ops(trace, ops)
                if scopes and wanted(scopes))
    if by == "rest":
        total = program_s - total
    return 1e3 * total / n

"""Persistent XLA compilation cache — the product default for sessions.

Cold start for a live session is dominated by XLA compiles: the fused tick
program, the B-branch speculative rollout, and the warmup probes all
compile from scratch in every fresh process. The persistent cache (keyed
by HLO hash, so stale entries are impossible) turns every later process's
cold start into a disk read; the benchmark's one-process-a-run cells and
a game relaunching on a player's machine hit the same path.

:func:`ensure_persistent_compilation_cache` is the ONE place this
repository chooses a cache directory: ``SessionBuilder`` and
``MatchServer`` call it on construction, ``tests/conftest.py`` at
import. The directory is placed from outside:
``JAX_COMPILATION_CACHE_DIR`` (or an earlier ``jax.config`` call) wins and
nothing is set here; otherwise the cache lives at ``.jax_cache`` in the
checkout this package was imported from — a fixed path whatever the
working directory, so every process of one checkout shares it.
``JAX_ENABLE_COMPILATION_CACHE=0`` is jax's own off switch.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

_DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def ensure_persistent_compilation_cache() -> str:
    """Enable JAX's persistent compilation cache; returns the directory in
    effect. A directory configured from outside is left alone."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        cache_dir = _DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Session programs compile fast individually (the fused tick is one
    # big program but the warmup probes are tiny) — cache them all, not
    # just the ones above jax's default size/time floors.
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


# -- compile counters ---------------------------------------------------
#
# Process-wide counts of executables obtained and of persistent-cache
# hits/misses, fed by jax's monitoring events. This is the observable the
# serving layer's no-recompile-on-churn contract is asserted against:
# MatchServer admits/retires matches into fixed slots with traced indices,
# so after warmup `compile_counters()["backend_compiles"]` must not move —
# tests/test_batched_sessions.py snapshots it around a churn phase, and
# benchmark/run.py around every window (``window.executables_built``).
#
# ``backend_compiles`` counts every executable a jit cache miss had to
# obtain — compiled by the backend OR loaded from the persistent cache
# (jax times both under backend_compile_duration). ``cache_hits`` /
# ``cache_misses`` split it: misses are the real backend compiles.

_COUNTERS = {
    "backend_compiles": 0,
    "cache_tasks": 0,
    "cache_hits": 0,
    "cache_misses": 0,
}
# One record per executable obtained: {"ms": wall_ms, "fingerprint": the
# jitted function's name as jax reports it, "cache": "hit" | "miss" | None
# (None: the persistent cache was not consulted)}. This is the
# decomposition of cold-start cost the autoscale rows need —
# scale_up_latency p50≈13.5s is a child JAX boot, and this says how much
# of it was XLA compiling.
_COMPILE_EVENTS: List[dict] = []
_LISTENERS_INSTALLED = False


def install_compile_listeners() -> bool:
    """Register jax monitoring listeners feeding :func:`compile_counters`.

    Idempotent and process-global (installation is once-per-process by
    design: the counters are). Returns True once the listeners are live.
    """
    global _LISTENERS_INSTALLED
    if _LISTENERS_INSTALLED:
        return True
    from jax import monitoring

    from bevy_ggrs_tpu.obs.trace import record_compile

    # A request's cache verdict event precedes its duration event; carried
    # from one listener to the other.
    verdict: Optional[str] = None

    def _on_event(event: str, **kwargs) -> None:
        nonlocal verdict
        # tasks_using_cache fires once per process, at the first compile
        # that consults the persistent cache; cache_hits / cache_misses
        # fire once per compile request that consulted it.
        if event.endswith("/tasks_using_cache"):
            _COUNTERS["cache_tasks"] += 1
        elif event.endswith("/cache_hits"):
            _COUNTERS["cache_hits"] += 1
            verdict = "hit"
        elif event.endswith("/cache_misses"):
            _COUNTERS["cache_misses"] += 1
            verdict = "miss"

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        nonlocal verdict
        if event.endswith("/backend_compile_duration"):
            _COUNTERS["backend_compiles"] += 1
            event = {
                "ms": float(duration) * 1000.0,
                "fingerprint": str(kwargs.get("fun_name", "")),
                "cache": verdict,
            }
            _COMPILE_EVENTS.append(event)
            # As a program event too (series compile_ms, ggrs/compile on
            # the trace clock): a run that obtained an executable where it
            # must not says WHICH program.
            record_compile(event["fingerprint"], event["ms"], verdict)
            verdict = None

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _LISTENERS_INSTALLED = True
    return True


def compile_counters() -> dict:
    """Snapshot of the process-wide compile/cache counters (a copy).

    Zeros until :func:`install_compile_listeners` has been called (and
    only events after installation are counted — snapshot a baseline and
    compare deltas)."""
    return dict(_COUNTERS)


def compile_events() -> List[dict]:
    """Per-executable wall-time records (copies), in occurrence order."""
    return [dict(e) for e in _COMPILE_EVENTS]


def compile_summary() -> dict:
    """Aggregate of the per-compile wall times: the
    ``ggrs_xla_compile_ms`` summary obs/prom.py exports and the
    compile-cost column autoscale rows carry. Empty-safe (all zeros
    before the first post-installation compile)."""
    times = sorted(e["ms"] for e in _COMPILE_EVENTS)
    if not times:
        return {
            "count": 0,
            "total_ms": 0.0,
            "mean_ms": 0.0,
            "p50_ms": 0.0,
            "max_ms": 0.0,
            "fingerprints": [],
        }
    total = float(sum(times))
    return {
        "count": len(times),
        "total_ms": round(total, 3),
        "mean_ms": round(total / len(times), 3),
        "p50_ms": round(times[len(times) // 2], 3),
        "max_ms": round(times[-1], 3),
        "fingerprints": sorted(
            {e["fingerprint"] for e in _COMPILE_EVENTS if e["fingerprint"]}
        ),
    }


# -- per-executable cost/memory analysis, and the phase map ---------------
#
# The monitoring listeners see durations, never executables, so the cost
# observatory is an explicit capture: callers that own a jitted function
# (executor warmup) register it once under a stable
# name and this module prices it via the AOT path —
# ``jitted.lower(*args).compile()`` then ``cost_analysis()`` (flops,
# bytes accessed) and ``memory_analysis()`` (argument/output/temp/
# generated-code bytes, summed into ``hbm_peak_bytes``: the number that
# decides how many lanes fit a device). From the same compiled object it
# keeps the PHASE MAP: which instruction of the optimized module was traced
# under which device scopes (``obs/trace.py`` ``device_scope``), read off
# the ``metadata={op_name="..."}`` of ``as_text()``. A device trace names
# an operation by its instruction's name and nothing else, so the map is
# what turns a trace's operation times into device time by phase
# (``executable_phases``; the benchmark's ``trace_phase`` reader,
# ``tools/trace_spans.py server``).
#
# The capture's compile keys the persistent cache WITH the module's
# metadata (``jax_compilation_cache_include_metadata_in_key``, for that one
# compile): jax's default key drops it, so an executable that another
# checkout of this program cached before the scopes existed, or with other
# scopes, is a hit for the live call and carries that checkout's
# ``op_name``s. XLA's passes do not read metadata: the instruction names
# this compile yields are those of the executable that runs. Call it during
# warmup, before any compile counters are snapshotted for churn gates; the
# AOT lowering re-traces the program (seconds at large S) and a capture's
# first compile at a new tree is a real one.

_EXEC_COSTS: Dict[str, dict] = {}
_EXEC_PHASES: Dict[str, dict] = {}

_MEMORY_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)

_METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"

# One instruction of ``as_text()``: ``  [ROOT ]%name = type opcode(...)``,
# inside ``[ENTRY ]%computation (...) -> type {`` ... ``}``. The opcode is
# the first lower-case word before a ``(`` (types hold ``T(8,128)`` tiles
# and ``S(1)`` memory spaces, in capitals).
_COMPUTATION = re.compile(r"^(ENTRY )?%?([^\s(]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = (.*)$")
_OPCODE = re.compile(r"(?<![\w.%-])([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.-]+)"
)
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.-]+)")
# Whose called computations run as operations of their own (a fusion's and
# a reduction's are part of the one operation the trace times).
_CONTROL_FLOW = frozenset(("while", "conditional", "call"))
# Never an event of a device trace: they move no data.
_FREE_OPCODES = frozenset(
    ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
)


class OpScopes(NamedTuple):
    """One instruction of an optimized module that can take device time:
    where it stands and the device scopes it lies under, outermost first
    (``()``: none); ``own``: whether its own metadata named them."""

    computation: str
    entry: bool
    name: str
    opcode: str
    scopes: Tuple[str, ...]
    own: bool = True


def _operands(rest: str, start: int) -> List[str]:
    """The operand names of ``opcode(...)``, ``start`` just past its
    ``(``."""
    depth, end = 1, start
    while depth and end < len(rest):
        depth += {"(": 1, ")": -1}.get(rest[end], 0)
        end += 1
    return _OPERAND.findall(rest[start:end])


def op_scopes(hlo_text: str) -> List[OpScopes]:
    """Every instruction of ``compiled.as_text()`` that a device trace can
    time (not the inside of a fusion or of a reduction's ``to_apply``, and
    no parameter, constant, tuple or bitcast), with the ``ggrs/<scope>``
    parts of its ``op_name`` in order.

    An instruction the compiler made WITHOUT metadata (a layout copy, a
    prefetch into the faster memory, what a pass re-made of a ``cumsum``)
    serves the operation it feeds: it takes the scopes of the nearest
    instruction with metadata of its own that uses its result, in its
    computation (through tuples and bitcasts; of several, the first in the
    program's order). One that feeds none (a loop's carried copy, an
    output's) lies under the scopes of the ``while`` / ``conditional`` that
    runs its computation, and in the entry computation under none."""
    from bevy_ggrs_tpu.obs.trace import TRACE_PREFIX

    scope = re.compile(re.escape(TRACE_PREFIX) + r"(\w+)")
    inside = set()           # computations that are part of one operation
    caller: Dict[str, tuple] = {}  # computation -> (computation, scopes)
    rows = []
    # Per computation, for the walk from an instruction to its users:
    # name -> own scopes (free opcodes too), name -> users in order.
    own: Dict[str, Dict[str, tuple]] = {}
    users: Dict[str, Dict[str, list]] = {}
    computation, entry = "", False
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            entry, computation = bool(head.group(1)), head.group(2)
            own[computation], users[computation] = {}, {}
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(rest)
        opcode = op.group(1) if op else ""
        op_name = _OP_NAME.search(rest)
        scopes = (tuple(dict.fromkeys(scope.findall(op_name.group(1))))
                  if op_name else ())
        own[computation][name] = scopes
        for operand in (_operands(rest, op.end()) if op else ()):
            users[computation].setdefault(operand, []).append(name)
        if opcode in _FREE_OPCODES:
            continue
        called = _CALLED.findall(rest)
        for group in _BRANCHES.findall(rest):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        if opcode in _CONTROL_FLOW:
            for c in called:
                caller.setdefault(c, (computation, name))
        else:
            inside.update(called)
        rows.append(OpScopes(computation, entry, name, opcode, scopes))

    resolved: Dict[tuple, tuple] = {}

    def lies_under(comp: str, name: str) -> Tuple[str, ...]:
        key = (comp, name)
        if key not in resolved:
            resolved[key] = ()      # a cycle cannot be: the text is a DAG
            found = own[comp].get(name, ())
            seen, frontier = {name}, [name]
            while frontier and not found:   # nearest users first
                nxt = []
                for n in frontier:
                    for user in users[comp].get(n, ()):
                        if user not in seen:
                            seen.add(user)
                            nxt.append(user)
                found = next((own[comp][u] for u in nxt if own[comp][u]), ())
                frontier = nxt
            if not found and comp in caller:
                found = lies_under(*caller[comp])
            resolved[key] = found
        return resolved[key]

    return [
        r if r.scopes else r._replace(
            scopes=lies_under(r.computation, r.name), own=False)
        for r in rows if r.computation not in inside
    ]


def _compile_keyed_with_metadata(lowered):
    import jax

    before = getattr(jax.config, _METADATA_IN_KEY)
    jax.config.update(_METADATA_IN_KEY, True)
    try:
        return lowered.compile()
    finally:
        jax.config.update(_METADATA_IN_KEY, before)


def record_executable_cost(name: str, jitted, *args, **kwargs) -> dict:
    """Price ``jitted`` (a ``jax.jit`` callable) for call args once under
    ``name``, and keep its phase map (:func:`executable_phases`); later
    calls with the same name return the cached record.
    A column the backend does not report (``cost_analysis`` without a
    ``flops`` entry, ``memory_analysis`` returning None) is absent from
    the record; an error lowering or compiling propagates — the live call
    would hit it too.
    """
    if name in _EXEC_COSTS:
        return dict(_EXEC_COSTS[name])
    out: Dict[str, float] = {}
    compiled = _compile_keyed_with_metadata(jitted.lower(*args, **kwargs))
    ca = compiled.cost_analysis() or {}
    if "flops" in ca:
        out["flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    ma = compiled.memory_analysis()
    if ma is not None:
        for attr, key in _MEMORY_FIELDS:
            out[key] = float(getattr(ma, attr))
        out["hbm_peak_bytes"] = sum(out[key] for _, key in _MEMORY_FIELDS)
    _EXEC_COSTS[name] = out
    rows = op_scopes(compiled.as_text())
    _EXEC_PHASES[name] = {
        "ops": {r.name: r.scopes for r in rows if r.scopes},
        "inherited": sum(bool(r.scopes) and not r.own for r in rows),
        "unscoped": sum(not r.scopes for r in rows),
    }
    return dict(out)


def executable_costs() -> Dict[str, dict]:
    """Snapshot of every priced executable: name -> cost record."""
    return {k: dict(v) for k, v in _EXEC_COSTS.items()}


def executable_phases() -> Dict[str, dict]:
    """Snapshot of every captured executable's phase map: name ->
    ``{"ops": {instruction name: (scope, ...)}, "inherited": n,
    "unscoped": n}``. ``ops`` holds the instructions that lie under a
    device scope, outermost scope first: an operation's PHASE is the first
    one, and it lies under every scope named. ``inherited`` of them had no
    metadata of their own and took the scopes of the operation they feed or
    of their loop (:func:`op_scopes`); ``unscoped`` counts the instructions
    left with none. An executable whose program has no scope has an empty
    ``ops``: read that as "no map", never as zeros."""
    return {k: dict(v, ops=dict(v["ops"])) for k, v in _EXEC_PHASES.items()}

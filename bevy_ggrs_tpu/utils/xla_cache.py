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
from typing import Dict, List, Optional

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


# -- per-executable cost/memory analysis --------------------------------
#
# The monitoring listeners see durations, never executables, so the cost
# observatory is an explicit capture: callers that own a jitted function
# (executor warmup) register it once under a stable
# name and this module prices it via the AOT path —
# ``jitted.lower(*args).compile()`` then ``cost_analysis()`` (flops,
# bytes accessed) and ``memory_analysis()`` (argument/output/temp/
# generated-code bytes, summed into ``hbm_peak_bytes``: the number that
# decides how many lanes fit a device). The AOT compile re-traces, but
# its backend compile is a persistent-cache hit of the HLO the live jit
# call already compiled — call it during warmup, before any compile
# counters are snapshotted for churn gates.

_EXEC_COSTS: Dict[str, dict] = {}

_MEMORY_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)


def record_executable_cost(name: str, jitted, *args, **kwargs) -> dict:
    """Price ``jitted`` (a ``jax.jit`` callable) for call args once under
    ``name``; later calls with the same name return the cached record.
    A column the backend does not report (``cost_analysis`` without a
    ``flops`` entry, ``memory_analysis`` returning None) is absent from
    the record; an error lowering or compiling propagates — the live call
    would hit it too.
    """
    if name in _EXEC_COSTS:
        return dict(_EXEC_COSTS[name])
    out: Dict[str, float] = {}
    compiled = jitted.lower(*args, **kwargs).compile()
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
    return dict(out)


def executable_costs() -> Dict[str, dict]:
    """Snapshot of every priced executable: name -> cost record."""
    return {k: dict(v) for k, v in _EXEC_COSTS.items()}

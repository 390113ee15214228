#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it holds ``jax.devices()[0]`` from start to finish and spawns
nothing. It fails at once (exit 2, no result line) without a TPU. It loads
the cell named in ``BENCHMARK.json`` — a configuration file, a traffic file
and a loop kind found by their names — warms that cell's shapes (set-up),
measures for ``--seconds``, decides ``correct`` outside the window, and
prints the contract's one JSON object as its last line: with ``--trace 0``
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics and
the device's busy time from a ``jax.profiler`` trace of the window; its
last key, ``compared``, holds every number ``correct`` compared beside its
limit, and the same are the last lines of standard error.

This file knows no cell, configuration, title or metric by name.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import toy_files  # noqa: E402


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_cell(workload: str, overrides: dict | None = None):
    """(manifest, cell, configuration, traffic) for one workload name."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = _load(os.path.join(ROOT, entry["file"]))
    traffic = _load(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    overrides = overrides or {}
    return (manifest, cell, _merged(config, overrides.get("config")),
            _merged(traffic, overrides.get("traffic")))


def load_toy(workload: str) -> dict:
    """The ``overrides`` that shrink a cell to the rehearsal's toy size
    (tests/benchmark, on the CPU): data beside what it shrinks. A cell
    whose mix needs a size of its own has ``toy/<cell>.json``; every other
    cell of a loop kind shares ``toy/<loop kind>.json``."""
    _, _, config, _ = load_cell(workload)
    tried = toy_files(workload, config["driver"])
    for rel in tried:
        if os.path.isfile(os.path.join(ROOT, rel)):
            toy = _load(os.path.join(ROOT, rel))
            return {k: toy[k] for k in ("config", "traffic") if k in toy}
    raise FileNotFoundError(
        f"cell {workload!r} has no toy size for the rehearsal: add "
        f"{tried[1]} (every cell of loop kind {config['driver']!r}) or "
        f"{tried[0]} (this cell alone)")


def metric_entries(manifest: dict, group: str, workload: str) -> list:
    """The metrics of ``end_to_end`` / ``per_layer`` this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def read_metrics(entries: list, folder: str, results) -> dict:
    """Each metric through the reader its own data file names. A reader
    that finds nothing returns nothing and the metric is left out."""
    specs = [(m, _load(os.path.join(HERE, folder, m["name"] + ".json")))
             for m in entries]
    # Metrics computed from another metric come after it.
    specs.sort(key=lambda ms: "program_metric" in ms[1])
    out = {}
    for m, spec in specs:
        reader = importlib.import_module(f"benchmark.readers.{spec['kind']}")
        value = reader.read(spec, results)
        if value is not None:
            results.values[m["name"]] = value
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             control: str | None = None, require_tpu: bool = True,
             overrides: dict | None = None, emit=print):
    """Run the cell; returns (exit code, result dict or None). ``control``
    swaps in one of the title's deliberately wrong schedules;
    ``require_tpu=False`` and ``overrides`` (``load_toy``) exist for the CPU
    tests under tests/benchmark and are reachable from no command-line
    flag."""
    manifest, cell, config, traffic = load_cell(workload, overrides)
    marks = {}

    def mark(name):
        marks[name] = time.perf_counter() - T_PROCESS

    import jax

    mark("jax_imported")
    devices = jax.devices()
    mark("devices_up")
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        print(f"run.py: {workload} needs {cell['chips']} TPU chip(s); jax "
              f"found {len(devices)} x {dev.platform!r} ({dev.device_kind}); "
              "nothing was run", file=sys.stderr)
        return 2, None
    peaks_table = _load(os.path.join(HERE, "peaks.json"))
    if dev.platform == "tpu" and dev.device_kind not in peaks_table:
        print(f"run.py: no peaks for device kind {dev.device_kind!r} in "
              "benchmark/peaks.json", file=sys.stderr)
        return 2, None

    from bevy_ggrs_tpu.utils import xla_cache

    from benchmark.drivers.common import Comparison, Context
    from benchmark.readers.common import Results
    from benchmark.reduce import stats
    from benchmark.reduce import trace as reduce_trace

    # The program's one cache resolver: JAX_COMPILATION_CACHE_DIR if set,
    # else <checkout>/.jax_cache — a fixed path inside the checkout.
    cache_dir = xla_cache.ensure_persistent_compilation_cache()
    xla_cache.install_compile_listeners()

    # The traced run keeps the profiler on for the first ``trace_window_s`` of
    # a loop of ``traced_run_s`` (both the mix's): a program of a million
    # small operations a second fills the device's trace buffer in two
    # seconds, and collecting it costs tens of seconds.
    trace_window_s = None
    if trace:
        annotate = jax.profiler.TraceAnnotation
        seconds = min(seconds, float(traffic.get("traced_run_s", seconds)))
        trace_window_s = float(traffic.get("trace_window_s", seconds))
    else:
        annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    title = importlib.import_module(f"benchmark.titles.{config['title']}")
    ctx = Context(
        config=config, traffic=traffic, seed=int(seed), trace=bool(trace),
        control=control, title=title, annotate=annotate,
        reference=importlib.import_module(
            f"benchmark.reference.{title.REFERENCE}"),
    )
    driver = importlib.import_module(
        f"benchmark.drivers.{config['driver']}").Driver(ctx)
    mark("program_imported")
    driver.setup(mark)
    mark("driver_ready")

    # The program's own timers (traced run only), read from the window on.
    program_series = (driver.program_metrics.series
                      if driver.program_metrics is not None else {})
    series_base = {k: len(v) for k, v in program_series.items()}
    compiles0 = xla_cache.compile_counters()
    traced = {}
    window_span = contextlib.ExitStack()

    def stop_profiler() -> None:
        """Close the window's span and collect the trace. jax.profiler's
        stop_trace() also exports a trace-viewer file, which doubles the
        wait (92 s against 47 s for 1.9 s of the server, PR 23), so the
        session is held directly and its XSpace parsed from memory."""
        t = time.perf_counter()
        window_span.close()
        traced["xspace"] = session.stop()
        traced["stop_profiler_s"] = time.perf_counter() - t

    if trace:
        from jax._src.lib import _profiler

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        session = _profiler.ProfilerSession(options)
        window_span.enter_context(annotate(reduce_trace.WINDOW_SPAN))
    setup_s = time.perf_counter() - T_PROCESS

    if trace and trace_window_s < seconds:
        window_s = driver.window(seconds, pause_at=trace_window_s,
                                 pause=stop_profiler)
    else:
        window_s = driver.window(seconds)

    compiles1 = xla_cache.compile_counters()
    built = compiles1["backend_compiles"] - compiles0["backend_compiles"]
    if trace and "xspace" not in traced:
        stop_profiler()
    mark_post = {k: v for k, v in traced.items() if k != "xspace"}
    stats_mem = dev.memory_stats() or {}
    if built:
        driver.mark_all_failed()

    comparisons = [Comparison("window.executables_built", built, 0)]
    t_check = time.perf_counter()
    comparisons += driver.check()
    mark_post["check_s"] = time.perf_counter() - t_check

    scalars = dict(driver.scalars)
    scalars["setup_s"] = setup_s
    results = Results(
        window_s=window_s, series=driver.series, scalars=scalars,
        counters=driver.counters(),
        program_series={k: v[series_base.get(k, 0):]
                        for k, v in program_series.items()},
        peaks=peaks_table.get(dev.device_kind),
        cost_shapes=driver.cost_shapes(),
    )
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(stats_mem.get("peak_bytes_in_use", 0)),
    }
    breakdown = None
    if trace:
        t_reduce = time.perf_counter()
        mark_post["xspace_bytes"] = len(traced["xspace"])
        tr = reduce_trace.load(traced.pop("xspace"))
        mark_post["trace_reduce_s"] = time.perf_counter() - t_reduce
        mark_post["trace_buffers_dropped"] = tr.dropped_at is not None
        win = reduce_trace.window_of(tr)
        results.trace, results.trace_window = tr, win
        device["busy_s"] = reduce_trace.busy_seconds(tr, win)
        device["window_s"] = win[1] - win[0]
        breakdown = {
            "device_ops": reduce_trace.top_ops(tr),
            "idle_gaps": reduce_trace.idle_gaps(tr, win),
        }
        metrics = read_metrics(
            metric_entries(manifest, "per_layer", workload),
            "layer_metrics", results)
    else:
        metrics = read_metrics(
            metric_entries(manifest, "end_to_end", workload),
            "end_to_end", results)

    emit(json.dumps({"info": "run", "workload": workload, "seed": int(seed),
                     "seconds": seconds, "trace": int(bool(trace)),
                     "control": control, "cache_dir": cache_dir,
                     "setup_s": setup_s, "window_s": window_s,
                     "setup_marks_s": marks, "after_window": mark_post,
                     "setup_compiles": {
                         k: compiles0[k] for k in
                         ("backend_compiles", "cache_hits", "cache_misses")},
                     "scalars": scalars}))
    for name, samples in sorted(driver.series.items()):
        if samples:
            emit(json.dumps({
                "info": "series", "name": name, "unit": "ms",
                "count": len(samples),
                "median": stats.percentile(samples, 50),
                "p95": stats.percentile(samples, 95),
                "ladder": {str(q): stats.percentile(samples, q)
                           for q in (5, 25, 75, 90, 97.5, 99)},
                "p95_has_10_beyond": stats.supported(len(samples), 95),
                "max": max(samples)}))
    for c in comparisons:
        emit(json.dumps({"info": "compare", "name": c.name, "value": c.value,
                         "limit": c.limit, "ok": c.ok}))
    result = {
        "correct": all(c.ok for c in comparisons),
        "attempted": int(driver.attempted),
        "failed": int(driver.failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    # Every number compared beside its limit: the result's last key, and
    # the last lines of standard error (what the driver's record keeps of
    # a run that is not correct).
    result["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                          for c in comparisons}
    for c in comparisons:
        print(f"compared {c.name} value={c.value!r} limit={c.limit!r} "
              f"{'ok' if c.ok else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    emit(json.dumps(result))
    return 0, result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument(
        "--control", default=None,
        help="run one of the title's deliberately wrong schedules (the "
             "lower-precision control); such a run must print correct: false")
    args = parser.parse_args(argv)
    rc, _ = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                     control=args.control)
    return rc


if __name__ == "__main__":
    sys.exit(main())

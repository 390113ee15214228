#!/usr/bin/env python3
"""Program spans and the device's idle gaps on ONE clock.

    python3 tools/trace_spans.py client [--ticks N] [--branches B]
    python3 tools/trace_spans.py server [--frames N] [--capacity S]
                                        [--title box_game|boids|particles]

Holds a ``jax.profiler`` session around N paced ticks of ``chip_smoke.py``'s
singleton P2P pair (peer 0 speculating, WAN loopback profile) or N served
frames of its 256-match SyncTest server, each with a real ``Metrics`` sink,
so every program span is a ``ggrs/<name>`` annotation in the xplane's host
plane beside ``XLA Ops``. Prints, and writes to
``chiprun_out/trace_spans_<mode>.json``:

- per span name: count, parent, median and total duration, and SELF time
  (duration minus what its direct children cover);
- the device's idle gaps charged to the INNERMOST span the host was in, by
  the benchmark's reducer (``benchmark/reduce/trace.py`` ``nest`` and
  ``idle_gaps``: gaps of 20 us or more by span, shorter ones summed as
  ``between_ops_under_20us``, what no span covers as ``unattributed``); the
  tool's own phases (``tool/sleep``, ``tool/far_end``, ``tool/readable``)
  name what is outside the program;
- ``server`` only: EVERY device operation of the traced stretch with its
  phase and its device scopes (``obs/trace.py`` ``device_scope``, read off
  the compiled tick by ``utils.xla_cache.executable_phases()``: the real
  sink arms that capture), its self time a dispatch of the batched tick and
  its share of the program, then the totals by phase and by scope
  (``device_ops`` / ``device_phases`` / ``device_scopes`` in the JSON): what
  a dispatch is made of, under names that survive a recompile. The join is
  the benchmark's (``benchmark/readers/trace_phase.py``).

Without a TPU the trace has no device plane and only the span table is
printed.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# The one definition of "innermost span" and of an idle gap's charge is the
# benchmark's reducer (jax-free until ``load``); ``nest`` is used from here.
from benchmark.reduce import trace as reduce_trace  # noqa: E402
from benchmark.reduce.trace import nest  # noqa: E402

PROGRAM, TOOL = "ggrs/", "tool/"
WINDOW = "tool/window"


# -- reading the trace ------------------------------------------------------


def host_lines(data):
    """{thread line name: [(name, start_s, end_s)]} of the program's and the
    tool's annotations in the host plane."""
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [
                (ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events
                if ev.name.startswith((PROGRAM, TOOL))
            ]
            if events:
                out.setdefault(line.name, []).extend(events)
    return out


def device_blocks(data):
    """Busy blocks [(start_s, end_s, busy_s)] of the first TPU, by the
    benchmark's own reduction (gaps under 20 us stay inside a block)."""
    for plane in sorted(data.planes, key=lambda p: p.name):
        if reduce_trace._device_ordinal(plane.name) is None:
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                blocks, _ = reduce_trace.reduce_ops(
                    (ev.name, ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events
                )
                return blocks
    return None


def span_table(instances):
    by_name = collections.defaultdict(list)
    for name, parent, dur, own in instances:
        by_name[name].append((parent, dur, own))
    rows = []
    for name, got in by_name.items():
        parents = collections.Counter(p for p, _, _ in got)
        durs = [d * 1e3 for _, d, _ in got]
        owns = [o * 1e3 for _, _, o in got]
        rows.append({
            "span": name, "count": len(got),
            "parent": parents.most_common(1)[0][0],
            "median_ms": statistics.median(durs),
            "self_median_ms": statistics.median(owns),
            "total_ms": sum(durs), "self_total_ms": sum(owns),
        })
    rows.sort(key=lambda r: -r["total_ms"])
    return rows


def charge_idle(blocks, spans, window):
    """Idle seconds of the device inside ``window`` by the innermost of
    one thread's ``spans``: the benchmark's own reduction
    (``reduce/trace.py`` ``idle_gaps`` over its ``nest``), every row."""
    trace = reduce_trace.Trace(
        spans=spans, modules={}, blocks={0: blocks}, op_self_s={}
    )
    return dict(reduce_trace.idle_gaps(trace, window, n=len(spans) + 2))


TICK_PROGRAM = "^jit__tick_impl"   # the [S]-vmapped batched tick
TICK_CAPTURE = "batched_tick"      # its name in ``executable_phases()``
NO_SCOPE = "(no scope; other programs' operations too)"
GAPS = "(between operations)"


def device_ops_table(trace, ops, program=TICK_PROGRAM):
    """``trace``'s operation self times (a ``reduce_trace.Trace``) joined
    with the phase map ``ops`` (``{instruction name: (scope, ...)}``), a
    dispatch of the programs that match ``program``: every operation with
    its phase (its first scope), the scopes inside it, its ms and its share
    of the program, and the totals by phase and by scope. None where no
    such program ran on a device. The join is the benchmark's
    (``benchmark/readers/trace_phase.py`` ``scoped_ops``)."""
    from benchmark.readers.trace_phase import scoped_ops

    program_s, n = reduce_trace.program_time(
        trace, program, (float("-inf"), float("inf")))
    if not trace.op_self_s or not n:
        return None
    per = 1e3 / n   # seconds summed -> ms a dispatch (one a device)
    program_ms = 1e3 * program_s / n
    acc = collections.defaultdict(float)
    under_of = {}
    for key, seconds, under in scoped_ops(trace, ops):
        acc[key] += seconds * per
        under_of[key] = under
    rows = []
    phases = collections.defaultdict(float)
    scopes = collections.defaultdict(float)
    for key, ms in sorted(acc.items(), key=lambda kv: -kv[1]):
        under = under_of[key]
        rows.append({"op": key, "phase": under[0] if under else None,
                     "scopes": list(under[1:]), "ms": ms,
                     "share": ms / program_ms})
        phases[under[0] if under else NO_SCOPE] += ms
        for name in set(under[1:]):
            scopes[name] += ms
    phases[GAPS] = program_ms - sum(acc.values())
    return {"dispatches": n, "tick_program_ms": program_ms,
            "device_ops": rows, "device_phases": dict(phases),
            "device_scopes": dict(scopes)}


def device_ops_report(xspace, out) -> None:
    """Print the served tick's device time by operation, phase and scope,
    and keep it in ``out``."""
    from benchmark.readers.trace_phase import phase_map

    ops = phase_map(TICK_CAPTURE)
    table = device_ops_table(reduce_trace.load(xspace), ops)
    if table is None:
        print("no batched tick on a device plane: no operation table")
        return
    if not ops:
        print("the compiled tick holds no device scope: no phase map")
    out.update(table)
    program_ms = table["tick_program_ms"]
    print(f"\n{table['dispatches']} dispatches of the batched tick, "
          f"{program_ms:.4f} ms each; {len(table['device_ops'])} device "
          "operations, ms a dispatch:")
    print(f"{'operation':44s} {'phase':12s} {'scopes':34s} {'ms':>9s} "
          f"{'share':>7s}")
    for r in table["device_ops"]:
        print(f"{r['op']:44s} {str(r['phase'] or '-'):12s} "
              f"{'/'.join(r['scopes']) or '-':34s} {r['ms']:9.4f} "
              f"{100 * r['share']:6.2f}%")
    for title, key in (("by phase", "device_phases"),
                       ("by scope", "device_scopes")):
        print(f"\n{title} (ms a dispatch, share of the program):")
        for name, ms in sorted(table[key].items(), key=lambda kv: -kv[1]):
            print(f"  {name:40s} {ms:9.4f} {100 * ms / program_ms:6.2f}%")


def report(xspace, mode, extra):
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(xspace)
    lines = host_lines(data)
    if not lines:
        raise SystemExit("trace_spans: the host plane holds no ggrs/ span")
    main = max(lines.values(),
               key=lambda ev: sum(n.startswith(PROGRAM) for n, _, _ in ev))
    window = next(((s, e) for n, s, e in main if n == WINDOW), None)
    spans = [e for e in main if e[0] != WINDOW]
    instances, _ = nest(spans)
    rows = span_table(instances)
    out = {"mode": mode, "spans": rows, **extra}
    print(f"{'span':34s} {'parent':26s} {'n':>6s} {'median':>9s} "
          f"{'self med':>9s} {'total':>10s} {'self tot':>10s}  (ms)")
    for r in rows:
        print(f"{r['span']:34s} {str(r['parent']):26s} {r['count']:6d} "
              f"{r['median_ms']:9.4f} {r['self_median_ms']:9.4f} "
              f"{r['total_ms']:10.2f} {r['self_total_ms']:10.2f}")
    blocks = device_blocks(data)
    if blocks is None or window is None:
        print("no device plane in this trace (no TPU): idle gaps not charged")
    else:
        idle = charge_idle(blocks, spans, window)
        total = sum(idle.values())
        busy = (window[1] - window[0]) - total
        out["window_s"] = window[1] - window[0]
        out["idle_s"] = total
        out["idle_by_innermost_span"] = idle
        print(f"\nwindow {window[1] - window[0]:.4f} s, device idle "
              f"{total:.4f} s ({100 * total / (window[1] - window[0]):.2f} %),"
              f" busy {busy:.4f} s; idle by innermost span:")
        for name, v in sorted(idle.items(), key=lambda kv: -kv[1]):
            print(f"  {name:34s} {v:10.5f} s  {100 * v / total:6.2f} %")
        top = PROGRAM + ("stage_update" if mode == "client" else "serve_tick")
        # Idle inside the top span's instances, and the part of it no
        # narrower program span covers.
        inside = sum(v for k, v in idle.items() if k.startswith(PROGRAM))
        if inside:
            out["idle_inside_program_s"] = inside
            out["idle_in_top_span_self_share"] = idle.get(top, 0.0) / inside
            print(f"idle inside program spans {inside:.5f} s, of which "
                  f"{100 * idle.get(top, 0.0) / inside:.2f} % in {top} "
                  "itself (no narrower span)")
        if mode == "server":
            device_ops_report(xspace, out)
    path = os.path.join(ROOT, "chiprun_out", f"trace_spans_{mode}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1)
    print("wrote", path)
    return out


# -- the two drives ---------------------------------------------------------


def profiler_session():
    import jax
    from jax._src.lib import _profiler

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    return _profiler.ProfilerSession(options)


def series_medians(metrics):
    return {k: statistics.median(v) for k, v in sorted(metrics.series.items())
            if k.endswith("_ms") and v}


def drive_client(ticks: int, branches: int, warmup: int):
    import jax

    import chip_smoke
    from bevy_ggrs_tpu.app import SessionType
    from bevy_ggrs_tpu.models import box_game
    from bevy_ggrs_tpu.obs.trace import attach_process_events
    from bevy_ggrs_tpu.session import PlayerType, SessionBuilder
    from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
    from bevy_ggrs_tpu.utils.metrics import Metrics

    annotate = jax.profiler.TraceAnnotation
    dt = chip_smoke.DT
    net = LoopbackNetwork(latency=2 * dt, jitter=1 * dt, loss=0.03, seed=5)
    clock = lambda: net.now  # noqa: E731
    apps = []
    for me in range(2):
        app = chip_smoke._box_app(branches if me == 0 else 0, clock)
        builder = (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(chip_smoke.PLAYERS)
            .with_max_prediction_window(chip_smoke.WINDOW)
            .with_fps(chip_smoke.FPS)
        )
        for h in range(chip_smoke.PLAYERS):
            builder.add_player(
                PlayerType.local() if h == me
                else PlayerType.remote(("peer", h)), h)
        app.insert_session(
            builder.start_p2p_session(net.socket(("peer", me)), clock=clock),
            SessionType.P2P)
        apps.append(app)
    a, b = apps

    def tick():
        net.advance(dt)
        with annotate("tool/update"):
            a.update(now=net.now)
        with annotate("tool/readable"):
            jax.block_until_ready(a.stage.runner.state)
        with annotate("tool/far_end"):
            b.update(now=net.now)

    for _ in range(warmup):
        tick()
    # The sink goes in after warm-up (sinks are read at every span), so
    # the series hold the traced ticks alone.
    metrics = Metrics()
    a.stage.metrics = a.stage.runner.metrics = metrics
    attach_process_events(a.stage)
    jax.block_until_ready((a.stage.runner.state, b.stage.runner.state))
    session = profiler_session()
    with annotate(WINDOW):
        t0 = time.perf_counter()
        for k in range(ticks):
            with annotate("tool/sleep"):
                while time.perf_counter() < t0 + k * dt:
                    time.sleep(max(0.0, t0 + k * dt - time.perf_counter()
                                   - 0.0015))
            tick()
        jax.block_until_ready((a.stage.runner.state, b.stage.runner.state))
    xspace = session.stop()
    a.stage.close()
    return xspace, {"ticks": ticks, "branches": branches,
                    "rollbacks": a.stage.runner.rollbacks_total,
                    "series_median_ms": series_medians(metrics)}


def served_title(name: str):
    """``(schedule, world, input spec)`` of a title at its served cell's
    size (``BENCHMARK.json``: box_game, the 1,024-boid flock on the MXU
    force, upstream's particle stress test)."""
    import chip_smoke

    if name == "boids":
        from bevy_ggrs_tpu.models import boids

        return (boids.make_schedule(kernel="mxu"),
                boids.make_world(1024, chip_smoke.PLAYERS).commit(),
                boids.INPUT_SPEC)
    if name == "particles":
        from bevy_ggrs_tpu.models import particles

        return (particles.make_schedule(),
                particles.make_world(chip_smoke.PLAYERS).commit(),
                particles.INPUT_SPEC)
    from bevy_ggrs_tpu.models import box_game

    return (box_game.make_schedule(),
            box_game.make_world(chip_smoke.PLAYERS).commit(),
            box_game.INPUT_SPEC)


def drive_server(frames: int, capacity: int, groups: int, warmup: int,
                 title: str = "box_game"):
    import jax
    import numpy as np

    import chip_smoke
    from bevy_ggrs_tpu.serve.server import MatchServer
    from bevy_ggrs_tpu.session import SessionBuilder
    from bevy_ggrs_tpu.utils.metrics import Metrics

    annotate = jax.profiler.TraceAnnotation
    metrics = Metrics()
    schedule, world, input_spec = served_title(title)
    server = MatchServer(
        schedule, world,
        chip_smoke.WINDOW, chip_smoke.PLAYERS, input_spec,
        capacity=capacity, stagger_groups=groups, num_branches=8,
        spec_frames=8, metrics=metrics,
    )
    server.warmup()
    offsets = np.random.RandomState(5).randint(0, 16, size=capacity)
    for k in range(capacity):
        session = (
            SessionBuilder(input_spec)
            .with_num_players(chip_smoke.PLAYERS)
            .with_max_prediction_window(chip_smoke.WINDOW)
            .with_check_distance(2)
            .start_synctest_session()
        )
        server.add_match(session, lambda frame, handle, k=k: np.uint8(
            (frame * 3 + handle * 5 + int(offsets[k])) % 16))
    for _ in range(warmup):
        server.run_frame()
    jax.block_until_ready(server.groups[-1].states)
    base = {k: len(v) for k, v in metrics.series.items()}
    session = profiler_session()
    with annotate(WINDOW):
        for _ in range(frames):
            with annotate("tool/run_frame"):
                server.run_frame()
        with annotate("tool/readable"):
            jax.block_until_ready([g.states for g in server.groups])
    xspace = session.stop()
    server.close()
    for k, v in metrics.series.items():
        del v[:base.get(k, 0)]
    return xspace, {"frames": frames, "capacity": capacity, "title": title,
                    "series_median_ms": series_medians(metrics)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("mode", choices=("client", "server"))
    p.add_argument("--ticks", type=int, default=600)
    p.add_argument("--branches", type=int, default=256)
    p.add_argument("--frames", type=int, default=4)
    p.add_argument("--capacity", type=int, default=256)
    p.add_argument("--groups", type=int, default=4)
    p.add_argument("--title", default="box_game",
                   choices=("box_game", "boids", "particles"),
                   help="server: the title hosted, at its served cell's size")
    p.add_argument("--warmup", type=int, default=None)
    args = p.parse_args(argv)
    from bevy_ggrs_tpu.utils import xla_cache

    xla_cache.ensure_persistent_compilation_cache()
    xla_cache.install_compile_listeners()
    if args.mode == "client":
        xspace, extra = drive_client(
            args.ticks, args.branches,
            180 if args.warmup is None else args.warmup)
    else:
        xspace, extra = drive_server(
            args.frames, args.capacity, args.groups,
            4 if args.warmup is None else args.warmup, args.title)
    import jax

    extra["platform"] = jax.devices()[0].platform
    print(json.dumps(extra, indent=1))
    report(xspace, args.mode, extra)
    return 0


if __name__ == "__main__":
    sys.exit(main())

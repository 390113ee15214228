"""The one span instrument (obs/trace.py): three sinks, one clock.

No timing assertions: counts, nesting and identity only.
"""

import collections
import gc

import jax
import numpy as np
import pytest

from bevy_ggrs_tpu.app import SessionType
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.obs import trace as obs_trace
from bevy_ggrs_tpu.obs.trace import (
    NULL_SPAN,
    Instrumented,
    SpanTracer,
    attach_process_events,
    detach_process_events,
    null_tracer,
)
from bevy_ggrs_tpu.session import PlayerType, SessionBuilder
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from bevy_ggrs_tpu.utils.metrics import Metrics, null_metrics
from tests.test_app import build_box_app, scripted

DT = 1.0 / 60.0


class Pair:
    """Two box_game apps over a lossy loopback; peer 0 speculates and
    carries the sinks under test."""

    def __init__(self, metrics=None, tracer=None, speculation=8):
        self.net = LoopbackNetwork(latency=2 * DT, jitter=DT, loss=0.03,
                                   seed=5)
        clock = lambda: self.net.now  # noqa: E731
        self.apps = []
        self.advance_calls = 0
        for me in range(2):
            app = build_box_app(
                input_fn=scripted, clock=clock,
                speculation=speculation if me == 0 else 0,
                metrics=metrics if me == 0 else None,
            )
            builder = (
                SessionBuilder(box_game.INPUT_SPEC)
                .with_num_players(2).with_max_prediction_window(8)
            )
            for h in range(2):
                builder.add_player(
                    PlayerType.local() if h == me
                    else PlayerType.remote(("peer", h)), h)
            session = builder.start_p2p_session(
                self.net.socket(("peer", me)), clock=clock)
            app.insert_session(session, SessionType.P2P)
            self.apps.append(app)
        self.a = self.apps[0]
        if tracer is not None:
            # Sinks are read at every span: assigning one after
            # construction takes effect, for the runner's executors too.
            self.a.stage.tracer = self.a.stage.runner.tracer = tracer
        inner = self.a.session.advance_frame

        def counted():
            self.advance_calls += 1
            return inner()

        self.a.session.advance_frame = counted

    def tick(self, n=1):
        for _ in range(n):
            self.net.advance(DT)
            for app in self.apps:
                app.update(now=self.net.now)

    def close(self):
        for app in self.apps:
            app.stage.close()


def _host_annotations(xspace):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                        if ev.name.startswith(("ggrs/", "test/"))]
    return out


def _ring_parents(tracer):
    """[(name, parent name or None)] of every closed span in the ring."""
    out, stack = [], []
    for ph, name, _ts, _args in tracer._well_formed_events():
        if ph == "B":
            out.append((name, stack[-1] if stack else None))
            stack.append(name)
        elif ph == "E":
            stack.pop()
    return out


class TestSharedClock:
    def test_program_spans_are_in_the_xplane_inside_their_parents(self):
        from jax._src.lib import _profiler

        pair = Pair(metrics=Metrics())
        pair.tick(60)  # handshake + warm-up
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        session = _profiler.ProfilerSession(options)
        with jax.profiler.TraceAnnotation("test/outer"):
            pair.tick(20)
        events = _host_annotations(session.stop())
        pair.close()
        by_name = collections.defaultdict(list)
        for name, s, e in events:
            by_name[name].append((s, e))
        (outer,) = by_name["test/outer"]
        parents = {
            "ggrs/stage_update": ["test/outer"],
            "ggrs/session_advance": ["ggrs/stage_update"],
            "ggrs/spec_host_dispatch": ["ggrs/stage_update"],
            "ggrs/tick_dispatch": ["ggrs/spec_host_dispatch"],
            "ggrs/tick_stage_args": ["ggrs/tick_dispatch",
                                     "ggrs/spec_commit"],
            "ggrs/tick_enqueue": ["ggrs/tick_dispatch", "ggrs/spec_commit"],
        }
        for name, allowed in parents.items():
            assert by_name[name], f"no {name} in the host plane"
            for s, e in by_name[name]:
                assert outer[0] <= s and e <= outer[1], name
                assert any(ps <= s and e <= pe
                           for p in allowed for ps, pe in by_name[p]), (
                    f"{name} at {s} lies in none of {allowed}")
        assert len(by_name["ggrs/stage_update"]) == 20

    def test_args_reach_the_tracer_ring(self):
        tracer = SpanTracer()
        pair = Pair(metrics=Metrics(), tracer=tracer)
        pair.tick(70)
        pair.close()
        args = {name: a for ph, name, _, a in tracer._events if ph == "B"}
        assert "frame" in args["tick_dispatch"]
        assert "frame" in args["session_advance"]
        assert args["tick_enqueue"]["program"] in ("fused", "absorb")


class TestExactCounts:
    def test_one_span_per_unit_of_work(self):
        metrics, tracer = Metrics(), SpanTracer()
        pair = Pair(metrics=metrics, tracer=tracer)
        pair.tick(60)
        base = {k: len(v) for k, v in metrics.series.items()}
        n_ring = len(tracer._events)
        calls0 = pair.advance_calls
        pair.tick(40)
        pair.close()
        grown = {k: len(v) - base.get(k, 0)
                 for k, v in metrics.series.items()}
        assert grown["stage_update_ms"] == 40
        assert grown["poll_ms"] == 40
        # The poll's two sides, which the STAGE asks its session for while
        # its own sink listens: one sample a tick each, inside the span.
        assert grown["poll_recv_ms"] == grown["poll_send_ms"] == 40
        for key in ("poll_recv_ms", "poll_send_ms"):
            assert all(v > 0.0 for v in metrics.series[key])
        assert all(r + s_ <= p for r, s_, p in zip(
            metrics.series["poll_recv_ms"], metrics.series["poll_send_ms"],
            metrics.series["poll_ms"]))
        assert grown["session_advance_ms"] == pair.advance_calls - calls0 > 0
        assert grown["tick_enqueue_ms"] == grown["tick_stage_args_ms"] > 0
        assert grown["spec_tree_build_ms"] > 0
        # The ring saw the same spans, each enqueue inside a dispatch.
        del_events = list(tracer._events)[n_ring:]
        probe = SpanTracer()
        probe._events.extend(del_events)
        family = collections.Counter(_ring_parents(probe))
        enq = {p: n for (name, p), n in family.items()
               if name == "tick_enqueue"}
        stg = {p: n for (name, p), n in family.items()
               if name == "tick_stage_args"}
        assert enq == stg
        assert set(enq) <= {"tick_dispatch", "spec_commit",
                            "speculate_dispatch"}
        assert family[("stage_update", None)] == 40
        assert family[("session_advance", "stage_update")] == (
            pair.advance_calls - calls0)

    def test_series_keys_of_existing_metrics_are_kept(self):
        metrics = Metrics()
        pair = Pair(metrics=metrics)
        pair.tick(90)
        pair.close()
        assert {"tick_dispatch_ms", "checksum_sync_ms",
                "spec_host_dispatch_ms"} <= set(metrics.series)
        assert "spec_tick_ms" not in metrics.series


class _Thing(Instrumented):
    def __init__(self, metrics=None, tracer=None):
        self._set_sinks(metrics, tracer)


class TestOffPath:
    def test_null_sinks_hand_out_the_shared_noop(self):
        thing = _Thing()
        assert thing.span("x") is NULL_SPAN
        assert thing.span("x", frame=3) is NULL_SPAN
        assert null_tracer.span("x") is NULL_SPAN
        with thing.span("x") as sp:
            pass
        assert sp.ms == 0.0

    def test_uninstrumented_pair_grows_no_series_and_no_ring(self):
        pair = Pair()
        stage, runner = pair.a.stage, pair.a.stage.runner
        assert stage.metrics is null_metrics and stage.tracer is null_tracer
        assert runner._fused.span("tick_enqueue") is NULL_SPAN
        before = len(obs_trace._PROCESS_SINKS)
        pair.tick(70)
        assert stage.span("stage_update") is NULL_SPAN
        assert null_metrics.summary() == {}
        assert len(obs_trace._PROCESS_SINKS) == before
        assert pair.a.frame > 0
        pair.close()

    def test_one_sink_is_enough(self):
        m, t = Metrics(), SpanTracer()
        with _Thing(metrics=m).span("a"):
            pass
        with _Thing(tracer=t).span("b", k=1):
            pass
        assert len(m.series["a_ms"]) == 1
        assert t.summary()["b"]["count"] == 1
        assert [e[0] for e in t._events] == ["B", "E"]

    def test_series_false_keeps_the_duration_for_the_site(self):
        m = Metrics()
        thing = _Thing(metrics=m)
        with thing.span("part", series=False) as sp:
            pass
        assert "part_ms" not in m.series and sp.ms >= 0.0
        with _Thing().timed_span("always") as sp:
            pass
        assert sp is not NULL_SPAN

    def test_tracer_with_its_own_clock_keeps_its_timeline(self):
        now = [5.0]
        t = SpanTracer(clock=lambda: now[0])
        m = Metrics()
        with _Thing(metrics=m, tracer=t).span("virt"):
            now[0] += 2.0
        (_, _, b_ts, _), (_, _, e_ts, _) = t._events
        assert e_ts - b_ts == 2_000_000
        assert m.series["virt_ms"][0] < 1000.0  # the series is wall time


class TestProcessEvents:
    @pytest.fixture(autouse=True)
    def _no_owner_left_by_an_earlier_test(self):
        """Owners are held weakly and an owner that was never closed
        leaves the hook idling: start each test from none."""
        for obj in list(obs_trace._PROCESS_SINKS):
            detach_process_events(obj)
        detach_process_events(_Thing())
        yield

    def test_gc_pause_hook_installs_once_and_goes_on_close(self):
        assert obs_trace._on_gc not in gc.callbacks
        m1, m2, t2 = Metrics(), Metrics(), SpanTracer()
        one, two = _Thing(metrics=m1), _Thing(metrics=m2, tracer=t2)
        assert not attach_process_events(_Thing())  # null sinks: nothing
        assert obs_trace._on_gc not in gc.callbacks
        assert attach_process_events(one) and attach_process_events(two)
        assert gc.callbacks.count(obs_trace._on_gc) == 1
        gc.collect(1)
        gc.collect()
        for m in (m1, m2):
            assert len(m.series["gc_pause_ms"]) >= 2
        gens = [a["generation"] for ph, name, _, a in t2._events
                if ph == "B" and name == "gc_pause"]
        assert 1 in gens and 2 in gens
        detach_process_events(one)
        assert obs_trace._on_gc in gc.callbacks
        n = len(m1.series["gc_pause_ms"])
        gc.collect()
        assert len(m1.series["gc_pause_ms"]) == n
        assert len(m2.series["gc_pause_ms"]) > 2
        detach_process_events(two)
        assert obs_trace._on_gc not in gc.callbacks

    def test_stage_with_a_sink_attaches_itself_until_close(self):
        metrics = Metrics()
        pair = Pair(metrics=metrics)
        assert pair.a.stage in obs_trace._PROCESS_SINKS
        assert pair.apps[1].stage not in obs_trace._PROCESS_SINKS
        gc.collect()
        assert metrics.series["gc_pause_ms"]
        pair.close()
        assert pair.a.stage not in obs_trace._PROCESS_SINKS
        assert obs_trace._on_gc not in gc.callbacks

    def test_a_dropped_owner_stops_receiving(self):
        m = Metrics()
        thing = _Thing(metrics=m)
        attach_process_events(thing)
        del thing
        gc.collect()
        assert not obs_trace._PROCESS_SINKS
        detach_process_events(_Thing())
        assert obs_trace._on_gc not in gc.callbacks

    def test_export_walks_a_snapshot_of_the_ring(self):
        """A collection during an export appends to the ring being
        walked (the owner is attached): the walk must not see it."""
        t = SpanTracer()
        thing = _Thing(tracer=t)
        attach_process_events(thing)
        for _ in range(500):
            with thing.span("x", k=1):
                pass
        old = gc.get_threshold()
        gc.set_threshold(1, 1, 1)
        try:
            trace = t.export_perfetto()
        finally:
            gc.set_threshold(*old)
            detach_process_events(thing)
        names = {e["name"] for e in trace["traceEvents"]}
        assert "x" in names and t.summary()["gc_pause"]["count"] > 0

    def test_compile_is_a_program_event_with_the_programs_name(self):
        from bevy_ggrs_tpu.utils import xla_cache

        xla_cache.install_compile_listeners()
        m, t = Metrics(), SpanTracer()
        thing = _Thing(metrics=m, tracer=t)
        attach_process_events(thing)
        try:
            salt = float(np.random.RandomState().randint(1 << 30))

            def fresh_span_probe(x):
                return x * 3.0 + salt

            jax.jit(fresh_span_probe)(np.float32(1.0)).block_until_ready()
        finally:
            detach_process_events(thing)
        assert len(m.series["compile_ms"]) >= 1
        programs = [a["program"] for ph, name, _, a in t._events
                    if ph == "I" and name == "compile"]
        assert any("fresh_span_probe" in p for p in programs)


class TestServedSpans:
    def test_group_tick_and_sessions_once_a_group(self):
        from tests.test_serve_faults import (
            inputs_for, make_server, make_synctest,
        )

        metrics, tracer = Metrics(), SpanTracer()
        srv = make_server(metrics=metrics, tracer=tracer, capacity=4)
        assert srv in obs_trace._PROCESS_SINKS
        for k in range(3):
            srv.add_match(make_synctest(), inputs_for(k))
        groups_live = len({h.group for h in srv._matches})
        for _ in range(6):
            srv.run_frame()
        srv.close()
        assert srv not in obs_trace._PROCESS_SINKS
        assert len(metrics.series["serve_tick_ms"]) == 6 * groups_live
        assert len(metrics.series["serve_sessions_ms"]) == 6 * groups_live
        family = collections.Counter(_ring_parents(tracer))
        assert family[("serve_sessions", "serve_tick")] == 6 * groups_live
        # No span per slot: the group's span carries the count.
        args = [a for ph, name, _, a in tracer._events
                if ph == "B" and name == "serve_sessions"]
        assert all(set(a) == {"group", "matches"} for a in args)
        # The enqueue's series keeps its boundary: one sample a dispatch.
        in_ring = sum(n for (name, _), n in family.items()
                      if name == "serve_dispatch")
        assert len(metrics.series["serve_dispatch_ms"]) == in_ring
        assert family[("serve_dispatch", "serve_round")] >= 6 * groups_live
        assert len(metrics.series["native_batch_ms"]) in (
            0, len(metrics.series["serve_dispatch_ms"]))


# Series written once a group tick of the served frame (PR 35): four new
# spans, five sums of span ``serve_sessions`` with the polls' two sides,
# and the remainders.
GROUP_TICK_SERIES = (
    "serve_segment_ms", "serve_post_ms", "serve_arg_assembly_ms",
    "serve_branch_build_ms", "serve_tick_other_ms",
    "serve_sessions_other_ms", "serve_supervisor_ms", "serve_poll_ms",
    "serve_poll_recv_ms", "serve_poll_send_ms", "serve_local_inputs_ms",
    "serve_advance_ms", "serve_slo_ms",
)
SESSION_SUMS = (
    "serve_supervisor_ms", "serve_poll_ms", "serve_local_inputs_ms",
    "serve_advance_ms", "serve_slo_ms",
)


def _ring_durations_ms(tracer, name):
    """Durations of every closed span ``name`` in the ring, in order."""
    out, open_at = [], []
    for ph, n, ts, _args in tracer._well_formed_events():
        if n != name:
            continue
        if ph == "B":
            open_at.append(ts)
        elif ph == "E":
            out.append((ts - open_at.pop()) / 1000.0)
    return out


class TestServedBooks:
    """Every stretch of a served frame has a name, and what the names
    leave over is itself a series (``*_other_ms``)."""

    FRAMES = 6

    @pytest.fixture(scope="class")
    def run(self):
        from tests.test_serve_faults import (
            inputs_for, make_server, make_synctest,
        )

        metrics, tracer = Metrics(), SpanTracer()
        srv = make_server(metrics=metrics, tracer=tracer, capacity=4)
        # Warm-up dispatched once, outside any frame: not counted here.
        tracer._events.clear()
        warm = {k: len(v) for k, v in metrics.series.items()}
        for k in range(3):
            srv.add_match(make_synctest(), inputs_for(k))
        groups_live = len({h.group for h in srv._matches})
        for _ in range(self.FRAMES):
            srv.run_frame()
        srv.close()
        series = {k: v[warm.get(k, 0):] for k, v in metrics.series.items()}
        return series, tracer, self.FRAMES * groups_live

    def test_one_sample_a_group_tick_or_a_frame(self, run):
        series, _tracer, group_ticks = run
        for key in GROUP_TICK_SERIES:
            assert len(series[key]) == group_ticks, key
        for key in ("serve_frame_ms", "serve_frame_other_ms"):
            assert len(series[key]) == self.FRAMES, key
        # Opened only when there are rows: beside every read of the
        # checksums, and there is none before a group's first dispatch.
        assert 0 < len(series["serve_report_delivery_ms"]) == len(
            series["checksum_sync_ms"]) < group_ticks + 1
        # One spelling a key: the parent's second ones are gone.
        assert "serve_arg_assembly" not in series
        assert "serve_branch_build" not in series

    def test_every_new_span_has_its_parent(self, run):
        _series, tracer, group_ticks = run
        family = collections.Counter(_ring_parents(tracer))
        assert family[("serve_frame", None)] == self.FRAMES
        assert family[("serve_tick", "serve_frame")] == group_ticks
        assert family[("serve_segment", "serve_tick")] == group_ticks
        assert family[("serve_post", "serve_round")] == group_ticks
        assert family[("serve_arg_assembly", "serve_round")] == group_ticks
        delivered = family[("serve_report_delivery", "serve_frame")]
        assert delivered == family[("checksum_sync", "serve_frame")] > 0
        # One span a group tick or a round, none a match, slot or row.
        for name, keys in (("serve_frame", {"frame", "groups"}),
                           ("serve_segment", {"slots"}),
                           ("serve_post", {"slots"}),
                           ("serve_report_delivery", {"rows"})):
            args = [a for ph, n, _, a in tracer._events
                    if ph == "B" and n == name]
            assert args and all(set(a) == keys for a in args), name

    def test_remainders_are_remainders(self, run):
        series, tracer, _ = run
        for key in ("serve_sessions_other_ms", "serve_tick_other_ms",
                    "serve_frame_other_ms"):
            assert all(v >= -1e-9 for v in series[key]), key
        # serve_sessions = its five sums + other, to rounding.
        for i, span_ms in enumerate(series["serve_sessions_ms"]):
            parts = sum(series[k][i] for k in SESSION_SUMS)
            assert parts + series["serve_sessions_other_ms"][i] == (
                pytest.approx(span_ms, abs=1e-6))
        # The polls' two sides lie inside the poll's sum (SyncTest matches
        # have no poll: all three read 0).
        for r, s_, p in zip(series["serve_poll_recv_ms"],
                            series["serve_poll_send_ms"],
                            series["serve_poll_ms"]):
            assert 0.0 <= r + s_ <= p + 1e-9
        # A self time is what the span's children leave: the tick's and the
        # frame's against the ring (the ring's clock is in whole us).
        ticks = _ring_durations_ms(tracer, "serve_tick")
        inside = [a + b + c for a, b, c in zip(
            _ring_durations_ms(tracer, "serve_sessions"),
            _ring_durations_ms(tracer, "serve_segment"),
            _ring_durations_ms(tracer, "serve_round"))]
        for other, tick, covered in zip(series["serve_tick_other_ms"],
                                        ticks, inside):
            assert other == pytest.approx(tick - covered, abs=0.01)

    def test_arg_assembly_is_a_self_time(self, run):
        """``serve_arg_assembly_ms`` + the native plane's two calls + the
        ranking fit in the loop's span: the stage call is counted once."""
        series, tracer, _ = run
        loops = _ring_durations_ms(tracer, "serve_arg_assembly")
        native = series.get("native_batch_ms") or [0.0] * len(loops)
        builds = series["serve_branch_build_ms"]
        rank = sum(series.get("predictor_rank_ms", []))
        assert len(loops) == len(series["serve_arg_assembly_ms"])
        for own, nb, bb, loop in zip(series["serve_arg_assembly_ms"],
                                     native, builds, loops):
            assert own >= 0.0
            assert own + max(nb, bb) + rank <= loop + 0.005


class _BarePollSession:
    """A session written to the server's contract to the letter: a bare
    ``poll_remote_clients()``, no ``parts`` (everything else a SyncTest
    session's)."""

    def __init__(self, inner):
        self._inner = inner
        self.polls = 0

    def poll_remote_clients(self):
        self.polls += 1

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.mark.parametrize("sinks", [False, True])
def test_a_session_with_a_bare_poll_is_served_traced_or_not(sinks):
    """Listening changes no match's fate: the server hands its ``parts``
    list only to a poll that takes it; the contract's bare poll is called
    as ever, its time in ``serve_poll_ms`` and on neither side."""
    from bevy_ggrs_tpu.serve.faults import SlotHealth
    from tests.test_serve_faults import (
        inputs_for, make_server, make_synctest,
    )

    metrics = Metrics() if sinks else None
    srv = make_server(metrics=metrics, tracer=SpanTracer() if sinks else None,
                      capacity=4)
    session = _BarePollSession(make_synctest())
    handle = srv.add_match(session, inputs_for(0))
    before = len(metrics.series["serve_poll_ms"]) if sinks else 0
    for _ in range(4):
        srv.run_frame()
    assert srv.faults_total == 0
    assert srv.health_of(handle) is SlotHealth.HEALTHY
    assert session.polls == 4 and session.current_frame >= 4
    if sinks:
        polls = metrics.series["serve_poll_ms"][before:]
        assert len(polls) == 4 and all(v > 0.0 for v in polls)
        assert metrics.series["serve_poll_recv_ms"][before:] == [0.0] * 4
        assert metrics.series["serve_poll_send_ms"][before:] == [0.0] * 4
    srv.close()


def test_self_ms_is_what_direct_children_leave():
    """``_Span.self_ms``: the duration less the spans that closed directly
    under it, of any object on the thread; a grandchild counts once."""
    a, b = _Thing(metrics=Metrics()), _Thing(tracer=SpanTracer())
    with a.span("outer") as outer:
        with b.span("child") as child:
            with a.span("grandchild") as grandchild:
                pass
        with a.span("child") as second:
            pass
    assert grandchild.self_ms == grandchild.ms
    assert child.self_ms == pytest.approx(child.ms - grandchild.ms)
    assert outer.self_ms == pytest.approx(outer.ms - child.ms - second.ms)
    assert 0.0 <= outer.self_ms <= outer.ms
    assert NULL_SPAN.self_ms == 0.0
    # A bare marker (the admission path's ``first_frame`` stays open over
    # whole frames) is nobody's parent: the span around it is credited.
    with a.span("frame") as frame:
        marker = obs_trace.push_span("admission_first_frame")
        with a.span("tick") as tick:
            pass
    obs_trace.pop_span(marker)
    assert frame.self_ms == pytest.approx(frame.ms - tick.ms)


def test_trace_spans_tool_charges_idle_by_the_reducers_rule():
    """The tool's ``charge_idle`` is the benchmark reducer's ``idle_gaps``
    (one definition): innermost span, short gaps, what no span covers."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_spans", os.path.join(root, "tools", "trace_spans.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    from benchmark.reduce import trace as reduce_trace

    assert tool.nest is reduce_trace.nest
    spans = [("tool/run_frame", 1.0, 5.0), ("ggrs/serve_frame", 1.5, 4.5),
             ("ggrs/serve_tick", 2.0, 3.0)]
    blocks = [(0.5, 1.0, 0.5), (2.5, 2.75, 0.2), (4.0, 4.25, 0.25)]
    idle = tool.charge_idle(blocks, spans, (0.0, 6.0))
    assert idle == pytest.approx({
        "unattributed": 0.5 + 1.0, "tool/run_frame": 0.5 + 0.5,
        "ggrs/serve_frame": 0.5 + 1.0 + 0.25, "ggrs/serve_tick": 0.5 + 0.25,
        "between_ops_under_20us": 0.05,
    })


@pytest.mark.parametrize("mode", ["client", "server"])
def test_trace_spans_tool_reads_self_times(mode, tmp_path, monkeypatch):
    """tools/trace_spans.py at a toy size: the table holds the layer
    spans with their parents (no device plane on the CPU)."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_spans", os.path.join(root, "tools", "trace_spans.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.setattr(tool, "ROOT", str(tmp_path))
    if mode == "client":
        xspace, extra = tool.drive_client(ticks=20, branches=8, warmup=60)
        want = {"ggrs/stage_update": "tool/update",
                "ggrs/tick_enqueue": "ggrs/tick_dispatch",
                "ggrs/poll": "ggrs/stage_update"}
    else:
        xspace, extra = tool.drive_server(frames=2, capacity=4, groups=2,
                                          warmup=2)
        want = {"ggrs/serve_frame": "tool/run_frame",
                "ggrs/serve_tick": "ggrs/serve_frame",
                "ggrs/serve_sessions": "ggrs/serve_tick",
                "ggrs/serve_segment": "ggrs/serve_tick",
                "ggrs/serve_dispatch": "ggrs/serve_round",
                "ggrs/serve_post": "ggrs/serve_round",
                "ggrs/serve_report_delivery": "ggrs/serve_frame"}
    out = tool.report(xspace, mode, extra)
    rows = {r["span"]: r for r in out["spans"]}
    for name, parent in want.items():
        assert rows[name]["parent"] == parent
        assert 0.0 <= rows[name]["self_total_ms"] <= rows[name]["total_ms"]
    assert (tmp_path / "chiprun_out" / f"trace_spans_{mode}.json").exists()

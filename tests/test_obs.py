"""Observability subsystem: span tracer + Perfetto export validity, the
flight recorder over a seeded chaos session, desync forensics naming the
exact first divergent frame, instrumentation threading through the session
layer, and the disabled-path overhead guard (<2% on a 500-frame loopback
session)."""

import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bevy_ggrs_tpu import obs
from bevy_ggrs_tpu.chaos import ChaosPlan, ChaosSocket
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.obs.trace import NULL_SPAN, SpanTracer, null_tracer
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.session import (
    PlayerType,
    PredictionThreshold,
    SessionBuilder,
    SessionState,
)
from bevy_ggrs_tpu.session.supervisor import SessionSupervisor
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_p2p import FPS_DT, make_pair, scripted_input


def assert_valid_trace(trace):
    """Structural Perfetto validity: non-decreasing ts and properly
    nested, matched B/E events (what the trace-event importer needs)."""
    assert set(trace) >= {"traceEvents"}
    last_ts = -1
    stack = []
    for e in trace["traceEvents"]:
        if e["ph"] == "M":
            continue
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        assert e["ts"] >= last_ts, "timestamps out of order"
        last_ts = e["ts"]
        if e["ph"] == "B":
            stack.append(e["name"])
        elif e["ph"] == "E":
            assert stack, f"E without open span: {e['name']}"
            assert stack[-1] == e["name"], "mismatched B/E nesting"
            stack.pop()
        else:
            assert e["ph"] == "i"
    assert stack == [], f"unclosed spans: {stack}"


class TestSpanTracer:
    def test_nested_spans_export_valid_perfetto(self, tmp_path):
        t = SpanTracer(pid=3, process_name="peer-3")
        for i in range(5):
            with t.span("outer", i=i):
                with t.span("inner"):
                    pass
                t.instant("mark", frame=i)
        path = tmp_path / "trace.json"
        t.export_perfetto(str(path))
        trace = json.loads(path.read_text())
        assert_valid_trace(trace)
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"outer", "inner", "mark", "process_name"} <= names
        marks = [e for e in trace["traceEvents"] if e["ph"] == "i"]
        assert len(marks) == 5 and marks[0]["s"] == "t"
        assert all(e["pid"] == 3 for e in trace["traceEvents"])

    def test_summary_aggregates_per_name(self):
        t = SpanTracer()
        for _ in range(7):
            with t.span("phase"):
                pass
        s = t.summary()
        assert s["phase"]["count"] == 7
        assert s["phase"]["total_ms"] >= s["phase"]["max_ms"] > 0
        assert s["phase"]["mean_ms"] == pytest.approx(
            s["phase"]["total_ms"] / 7
        )

    def test_ring_eviction_still_exports_matched_events(self):
        # Capacity small enough that early B events are evicted while
        # their E events survive: export must repair, not crash or emit
        # orphans.
        t = SpanTracer(capacity=10)
        for _ in range(50):
            with t.span("a"):
                with t.span("b"):
                    pass
        assert_valid_trace(t.export_perfetto())

    def test_open_spans_are_closed_at_export(self):
        t = SpanTracer()
        span = t.span("still_open")
        span.__enter__()
        trace = t.export_perfetto()
        assert_valid_trace(trace)
        assert any(
            e["name"] == "still_open" and e["ph"] == "E"
            for e in trace["traceEvents"]
        )
        span.__exit__(None, None, None)

    def test_jsonl_round_trip(self, tmp_path):
        t = SpanTracer()
        with t.span("x"):
            t.instant("y")
        path = tmp_path / "events.jsonl"
        n = t.export_jsonl(str(path))
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert len(lines) == n == 3
        assert [l["ph"] for l in lines] == ["B", "I", "E"]

    def test_null_tracer_is_inert(self, tmp_path):
        with null_tracer.span("anything", key="val"):
            null_tracer.instant("nothing")
        assert null_tracer.summary() == {}
        assert null_tracer.export_perfetto()["traceEvents"] == []
        assert null_tracer.export_jsonl(str(tmp_path / "x")) == 0


class TestComponentTracks:
    """Span-name prefixes land on distinct named tids per process, so a
    merged fleet trace shows session / spec / server / relay rows instead
    of one flat track."""

    def test_prefixes_map_to_named_tracks(self):
        t = SpanTracer(pid=7)
        for name in ("net_poll", "spec_poll", "serve_tick", "relay_pump"):
            with t.span(name):
                pass
        trace = t.export_perfetto()
        assert_valid_trace(trace)
        track_of = {}
        names = {}
        for e in trace["traceEvents"]:
            if e["ph"] == "M" and e["name"] == "thread_name":
                names[e["tid"]] = e["args"]["name"]
            elif e["ph"] == "B":
                track_of[e["name"]] = e["tid"]
        # tid = tracer.tid * 4 + component offset (tracer.tid == 0 here).
        assert track_of == {
            "net_poll": 0, "spec_poll": 1, "serve_tick": 2, "relay_pump": 3,
        }
        assert names == {0: "session", 1: "spec", 2: "server", 3: "relay"}
        # Process identity is uniform across tracks.
        assert all(e["pid"] == 7 for e in trace["traceEvents"])

    def test_srv_prefix_shares_the_server_track(self):
        t = SpanTracer()
        with t.span("srv_watchdog"):
            pass
        with t.span("serve_tick"):
            pass
        tids = {
            e["name"]: e["tid"]
            for e in t.export_perfetto()["traceEvents"]
            if e["ph"] == "B"
        }
        assert tids["srv_watchdog"] == tids["serve_tick"] == 2

    def test_component_tids_never_collide_across_tracers(self):
        # tid stride is 4 == number of component offsets, so tracer tid 0
        # owns 0..3 and tracer tid 1 owns 4..7.
        a, b = SpanTracer(tid=0), SpanTracer(tid=1)
        for t in (a, b):
            with t.span("relay_pump"):  # highest offset (3)
                pass
            with t.span("net_poll"):    # lowest offset (0)
                pass
        tids_a = {e["tid"] for e in a.export_perfetto()["traceEvents"]
                  if e["ph"] != "M"}
        tids_b = {e["tid"] for e in b.export_perfetto()["traceEvents"]
                  if e["ph"] != "M"}
        assert tids_a == {0, 3} and tids_b == {4, 7}

    def test_export_carries_wall_anchor_for_merge(self):
        t = SpanTracer(pid=2, process_name="peer-2", wall_t0=1234.5)
        with t.span("net_poll"):
            pass
        trace = t.export_perfetto()
        assert trace["otherData"]["wall_t0"] == 1234.5
        assert trace["otherData"]["pid"] == 2
        assert trace["otherData"]["process_name"] == "peer-2"

    def test_mixed_component_spans_stay_valid(self):
        # Runtime order is globally LIFO; splitting by component track
        # must preserve per-track B/E matching too.
        t = SpanTracer()
        for i in range(20):
            with t.span("serve_tick", i=i):
                with t.span("net_poll"):
                    pass
                with t.span("spec_poll"):
                    pass
        trace = t.export_perfetto()
        assert_valid_trace(trace)
        per_track = {}
        for e in trace["traceEvents"]:
            if e["ph"] in ("B", "E"):
                per_track.setdefault(e["tid"], []).append(e)
        for tid, evs in per_track.items():
            stack = []
            for e in evs:
                if e["ph"] == "B":
                    stack.append(e["name"])
                else:
                    assert stack and stack[-1] == e["name"]
                    stack.pop()
            assert stack == []


class TestPromLabelExposition:
    def test_labeled_counters_export_as_labeled_samples(self):
        m = Metrics()
        m.count("frames_advanced", 42, labels={"match_slot": 3})
        m.observe("slot_ms", 1.5, labels={"match_slot": 3})
        text = obs.export_prometheus(m)
        assert 'ggrs_frames_advanced_total{match_slot="3"} 42' in text
        assert 'ggrs_slot_ms{match_slot="3",quantile="0.5"} 1.5' in text
        assert 'ggrs_slot_ms_count{match_slot="3"} 1' in text

    def test_type_line_once_per_family_across_label_sets(self):
        m = Metrics()
        for s in range(3):
            m.count("ticks", labels={"match_slot": s})
        text = obs.export_prometheus(m)
        assert text.count("# TYPE ggrs_ticks_total counter") == 1
        assert text.count("ggrs_ticks_total{") == 3

    def test_escaped_label_values_survive_exposition(self):
        m = Metrics()
        m.count("req", labels={"peer": 'p "quoted" \\ end'})
        text = obs.export_prometheus(m)
        line = next(
            l for l in text.splitlines()
            if l.startswith("ggrs_req_total{")
        )
        assert '\\"quoted\\"' in line and "\\\\" in line
        # The label block still parses as exactly one k="v" pair.
        assert line.count("{") == 1

    def test_overflow_bucket_exports_and_is_bounded(self):
        m = Metrics(label_cardinality=2)
        for s in range(50):
            m.count("ticks", labels={"match_slot": s})
        text = obs.export_prometheus(m)
        assert 'ggrs_ticks_total{overflow="true"} 48' in text
        assert "ggrs_label_sets_dropped_total 48" in text
        # Exposition stays bounded: 2 admitted + 1 overflow label set.
        assert text.count("ggrs_ticks_total{") == 3


class TestFlightRecorder:
    def test_health_transitions_and_counter_deltas(self):
        rec = obs.FlightRecorder(capacity=8)
        runner = SimpleNamespace(
            frame=0, rollbacks_total=0, rollback_frames_total=0
        )
        sup = SimpleNamespace(health=SimpleNamespace(name="HEALTHY"))
        rec.capture(runner=runner, supervisor=sup)
        runner.rollbacks_total, runner.rollback_frames_total = 1, 3
        sup.health = SimpleNamespace(name="QUARANTINED")
        r = rec.capture(runner=runner, supervisor=sup)
        assert r.rollbacks == 1 and r.resim_frames == 3
        assert r.rollback_depth == 3
        assert r.health_transition == ("HEALTHY", "QUARANTINED")
        assert rec.health_transitions() == [(0, "HEALTHY", "QUARANTINED")]
        assert rec.rollback_histogram() == {3: 1}
        # Bounded: 20 more captures keep only the newest 8 records.
        for _ in range(20):
            rec.capture(runner=runner)
        assert len(rec.records) == 8


def make_obs_peer(net, n, me, metrics=None, tracer=None):
    """A supervised peer with instrumentation threaded through the
    builder, runner, and supervisor (the one-wiring-point path)."""
    sock = net.socket(("peer", me))
    builder = (
        SessionBuilder(box_game.INPUT_SPEC)
        .with_num_players(n)
        .with_max_prediction_window(8)
    )
    for h in range(n):
        builder.add_player(
            PlayerType.local() if h == me else PlayerType.remote(("peer", h)), h
        )
    session = builder.start_p2p_session(
        sock, clock=lambda: net.now, metrics=metrics, tracer=tracer
    )
    runner = RollbackRunner(
        box_game.make_schedule(),
        box_game.make_world(n).commit(),
        max_prediction=8,
        num_players=n,
        input_spec=box_game.INPUT_SPEC,
        metrics=metrics,
        tracer=tracer,
    )
    sup = SessionSupervisor(session, runner, metrics=metrics)
    return session, runner, sup


class TestChaosTraceRoundTrip:
    def test_seeded_200_frame_chaos_session_round_trips(self, tmp_path):
        """Satellite: a seeded chaos session, fully instrumented; the
        Perfetto export validates structurally, the JSONL/frame artifacts
        round-trip, and the Prometheus snapshot carries the session-layer
        counters."""
        net = LoopbackNetwork()
        plan = ChaosPlan.generate(7, 3.0, (("peer", 0), ("peer", 1)))
        metrics = Metrics()
        tracer = SpanTracer(pid=0, process_name="peer-0")
        recorder = obs.FlightRecorder()
        peers = [
            make_obs_peer(net, 2, 0, metrics=metrics, tracer=tracer),
            make_obs_peer(net, 2, 1),
        ]
        for me, (session, _, _) in enumerate(peers):
            session.socket = ChaosSocket(
                session.socket, plan, clock=lambda: net.now, addr=("peer", me)
            )
        for _ in range(280):
            net.advance(FPS_DT)
            for i, (session, runner, sup) in enumerate(peers):
                session.poll_remote_clients()
                events = sup.tick(net.now)
                if session.current_state() != SessionState.RUNNING:
                    continue
                if not sup.should_advance():
                    continue
                try:
                    for h in session.local_player_handles():
                        session.add_local_input(
                            h, scripted_input(h, session.current_frame)
                        )
                    runner.handle_requests(session.advance_frame(), session)
                except PredictionThreshold:
                    pass
                if i == 0:
                    recorder.capture(
                        session=session,
                        runner=runner,
                        supervisor=sup,
                        events=events,
                    )

        session0 = peers[0][0]
        assert session0.current_frame >= 200

        # Perfetto: write, reload, validate structurally.
        trace_path = tmp_path / "trace.json"
        obs.export_perfetto(tracer, str(trace_path))
        trace = json.loads(trace_path.read_text())
        assert_valid_trace(trace)
        names = {e["name"] for e in trace["traceEvents"]}
        assert {
            "net_poll", "net_recv", "net_send", "advance_frame",
            "handle_requests", "sup_tick",
        } <= names

        # JSONL event stream and flight-recorder artifact round-trip.
        assert tracer.export_jsonl(str(tmp_path / "events.jsonl")) > 0
        n = recorder.export_jsonl(str(tmp_path / "frames.jsonl"))
        frames = [
            json.loads(l)
            for l in (tmp_path / "frames.jsonl").read_text().splitlines()
        ]
        assert len(frames) == n == len(recorder.records)
        # Records carry the frame timeline and per-peer telemetry.
        seqs = [f["seq"] for f in frames]
        assert seqs == sorted(seqs)
        assert frames[-1]["frame"] >= 200
        assert any(f["peers"] for f in frames)
        last_peer = frames[-1]["peers"]["('peer', 1)"]
        assert last_peer["remote_frame"] > 0
        assert last_peer["ack_frontier"] > 0
        # The chaos socket's injected faults landed in the records.
        assert sum(len(f["faults"]) for f in frames) > 0
        # Histogram totals agree with the raw records.
        hist = recorder.rollback_histogram()
        assert sum(hist.values()) == sum(
            1 for r in recorder.records if r.rollbacks
        )

        # Session-layer counters flowed into the shared sink (satellite:
        # metrics threading) and export as Prometheus text.
        assert metrics.counters["datagrams_in"] > 0
        assert metrics.counters["datagrams_out"] > 0
        assert metrics.counters["checksum_ballots"] > 0
        text = obs.export_prometheus(metrics, recorder)
        assert "ggrs_datagrams_in_total" in text
        assert "ggrs_datagrams_out_total" in text
        assert text.endswith("\n")


class TestDesyncForensics:
    def test_dump_names_exact_first_divergent_frame_and_fields(
        self, tmp_path
    ):
        """Acceptance: forced divergence -> both peers' forensics dumps
        identify the first divergent frame and the differing state
        fields."""
        net = LoopbackNetwork()
        peers = make_pair(net, desync_detection=1)
        forensics = [
            obs.DesyncForensics(
                s, r, out_dir=str(tmp_path / f"peer{i}"), tag=f"_p{i}"
            )
            for i, (s, r) in enumerate(peers)
        ]
        # Constant inputs at zero latency: repeat-last prediction is always
        # right, so no rollback ever re-simulates (and silently heals) the
        # perturbation below.
        const = lambda h, f: np.uint8(box_game.INPUT_UP)
        history = [{}, {}]  # full per-peer checksum history (session GCs)

        def step():
            net.advance(FPS_DT)
            for i, (session, runner) in enumerate(peers):
                session.poll_remote_clients()
                forensics[i].scan(session.events())
                if session.current_state() != SessionState.RUNNING:
                    continue
                for h in session.local_player_handles():
                    session.add_local_input(h, const(h, session.current_frame))
                try:
                    runner.handle_requests(session.advance_frame(), session)
                except PredictionThreshold:
                    continue
                history[i].update(session._local_checksums)

        for _ in range(40):
            step()
        assert all(s.current_state() == SessionState.RUNNING for s, _ in peers)
        assert not forensics[0].dumps and not forensics[1].dumps

        # Force the divergence: shift peer 1's world off-trajectory.
        victim_r = peers[1][1]
        comps = dict(victim_r.state.components)
        comps["translation"] = comps["translation"] + np.float32(1.0)
        victim_r.state = victim_r.state.replace(components=comps)

        for _ in range(40):
            step()

        assert forensics[0].dumps and forensics[1].dumps
        # Ground truth, from the complete histories the test kept.
        expected = min(
            f
            for f in set(history[0]) & set(history[1])
            if history[0][f] != history[1][f]
        )

        da, db = forensics[0].dumps[0], forensics[1].dumps[0]
        assert da["first_divergent_frame"] == expected
        assert db["first_divergent_frame"] == expected
        cmp = obs.DesyncForensics.compare(da, db)
        assert cmp["first_divergent_frame"] == expected
        assert "component/translation" in cmp["divergent_fields"]
        # The artifacts were written and are valid JSON with the schema.
        dumped = list((tmp_path / "peer0").glob("desync_p0_f*.json"))
        assert dumped
        on_disk = json.loads(dumped[0].read_text())
        assert on_disk["schema"] == da["schema"]
        # The replayable ingredients are present on each dump.
        assert da["breakdown"] and db["breakdown"]
        assert da["breakdown_source"] in ("ring", "current_state")
        assert db["local_checksums"]


class TestOverheadGuard:
    def test_null_tracer_overhead_under_2_percent_of_500_frame_session(self):
        """CI guard for the disabled path: measure the wall time of a
        500-frame loopback session (instrumentation present, all null),
        count how many spans an *enabled* tracer records per tick on the
        same workload, then directly time that many null-span operations
        for 500 ticks. Deterministic — no flaky two-full-run comparison."""
        def run_session(n_iters, tracer=None):
            net = LoopbackNetwork()
            peers = []
            for me in range(2):
                sock = net.socket(("peer", me))
                builder = (
                    SessionBuilder(box_game.INPUT_SPEC)
                    .with_num_players(2)
                    .with_max_prediction_window(8)
                )
                for h in range(2):
                    builder.add_player(
                        PlayerType.local() if h == me
                        else PlayerType.remote(("peer", h)),
                        h,
                    )
                session = builder.start_p2p_session(
                    sock, clock=lambda: net.now, tracer=tracer
                )
                runner = RollbackRunner(
                    box_game.make_schedule(),
                    box_game.make_world(2).commit(),
                    max_prediction=8,
                    num_players=2,
                    input_spec=box_game.INPUT_SPEC,
                    tracer=tracer,
                )
                peers.append((session, runner))
            ticks = 0
            for _ in range(n_iters):
                net.advance(FPS_DT)
                for session, runner in peers:
                    session.poll_remote_clients()
                    if session.current_state() != SessionState.RUNNING:
                        continue
                    for h in session.local_player_handles():
                        session.add_local_input(
                            h, scripted_input(h, session.current_frame)
                        )
                    try:
                        runner.handle_requests(
                            session.advance_frame(), session
                        )
                    except PredictionThreshold:
                        continue
                    ticks += 1
            return ticks

        # Baseline: the full 500-frame session on the null (default) path.
        t0 = time.perf_counter()
        ticks = run_session(500)
        baseline_s = time.perf_counter() - t0
        assert ticks >= 2 * 450  # both peers actually ran ~500 frames

        # Span volume: what an enabled tracer records on this workload.
        probe = SpanTracer()
        probe_ticks = run_session(60, tracer=probe)
        spans = sum(s["count"] for s in probe.summary().values())
        spans_per_tick = spans / max(probe_ticks, 1)

        # Direct cost of the disabled path at 2x that volume: what a
        # unified site pays with both sinks null (one ``self.span(...)``
        # call handing out the shared no-op, where there used to be a
        # null timer AND a null tracer span).
        off = RollbackRunner(
            box_game.make_schedule(), box_game.make_world(2).commit(),
            max_prediction=8, num_players=2, input_spec=box_game.INPUT_SPEC,
        )
        assert off.span("x") is NULL_SPAN
        n_ops = int(spans_per_tick * ticks * 2) + 1
        t0 = time.perf_counter()
        for _ in range(n_ops):
            with off.span("x", frames=1):
                pass
        null_cost_s = time.perf_counter() - t0

        assert null_cost_s < 0.02 * baseline_s, (
            f"null tracer cost {null_cost_s * 1e3:.2f} ms is >= 2% of the "
            f"{baseline_s * 1e3:.0f} ms baseline ({n_ops} ops, "
            f"{spans_per_tick:.1f} spans/tick)"
        )


class TestMetricsThreading:
    def test_session_layer_counters_flow_under_latency(self):
        """Satellite: mispredictions, ballots, and datagram counters land
        in the shared sink when the network forces rollbacks."""
        net = LoopbackNetwork(latency=3 * FPS_DT)
        metrics = Metrics()
        peers = []
        for me in range(2):
            sock = net.socket(("peer", me))
            builder = (
                SessionBuilder(box_game.INPUT_SPEC)
                .with_num_players(2)
                .with_max_prediction_window(8)
            )
            for h in range(2):
                builder.add_player(
                    PlayerType.local() if h == me
                    else PlayerType.remote(("peer", h)),
                    h,
                )
            session = builder.start_p2p_session(
                sock,
                clock=lambda: net.now,
                metrics=metrics if me == 0 else None,
            )
            runner = RollbackRunner(
                box_game.make_schedule(),
                box_game.make_world(2).commit(),
                max_prediction=8,
                num_players=2,
                input_spec=box_game.INPUT_SPEC,
            )
            peers.append((session, runner))
        for _ in range(90):
            net.advance(FPS_DT)
            for session, runner in peers:
                session.poll_remote_clients()
                if session.current_state() != SessionState.RUNNING:
                    continue
                for h in session.local_player_handles():
                    session.add_local_input(
                        h, scripted_input(h, session.current_frame)
                    )
                try:
                    runner.handle_requests(session.advance_frame(), session)
                except PredictionThreshold:
                    continue
        assert metrics.counters["mispredictions"] > 0
        assert len(metrics.series["misprediction_depth"]) > 0
        assert metrics.counters["datagrams_in"] > 0
        assert metrics.counters["datagrams_out"] > 0
        assert metrics.counters["checksum_ballots"] > 0
        assert metrics.counters["checksum_reports_rx"] > 0
        # The endpoint shares the sink the session was built with.
        ep = next(iter(peers[0][0]._endpoints.values()))
        assert ep.metrics is metrics

"""Telemetry is provably inert: the full observability stack (span
tracer, metrics, flight recorder, provenance sidecar) switched ON
produces bitwise-identical simulation results AND bitwise-identical
wire traffic versus the same run with everything OFF.

The wire-level check uses a test-local recorder at the very bottom of
the socket stack — present in BOTH runs, so the only variable is the
telemetry above it. Chaos faults ride a seeded plan whose RNG draws per
send must stay aligned; a sidecar that transmitted anything (or drew
randomness) would shift the fault schedule and fail the byte compare.
"""

import numpy as np
import pytest

from bevy_ggrs_tpu.chaos import ChaosPlan, ChaosSocket
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.obs import (
    FlightRecorder,
    ProvenanceLog,
    SidecarSocket,
    SpanTracer,
)
from bevy_ggrs_tpu.obs.ledger import SpeculationLedger
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner
from bevy_ggrs_tpu.session import (
    PlayerType,
    PredictionThreshold,
    SessionBuilder,
    SessionState,
)
from bevy_ggrs_tpu.state import checksum, combine64
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_batched_sessions import drive, make_core, make_script
from tests.test_p2p import FPS_DT, scripted_input


class WireRecorder:
    """Bottom-of-stack byte witness, identical in both runs."""

    def __init__(self, inner, log):
        self.inner = inner
        self.log = log

    def send_to(self, data, addr):
        self.log.append(("tx", bytes(data), addr))
        self.inner.send_to(data, addr)

    def receive_all(self):
        out = self.inner.receive_all()
        for addr, data in out:
            self.log.append(("rx", bytes(data), addr))
        return out

    def __getattr__(self, name):
        return getattr(self.inner, name)


def run_p2p(telemetry: bool, split: bool = False):
    """``split``: every poll is asked for its two sides
    (``poll_remote_clients(parts=...)``), as a caller with a sink asks."""
    net = LoopbackNetwork()
    plan = ChaosPlan.generate(11, 3.0, (("peer", 0), ("peer", 1)))
    wires = {0: [], 1: []}
    history = [{}, {}]
    recorder = FlightRecorder() if telemetry else None
    peers = []
    for me in range(2):
        sock = WireRecorder(net.socket(("peer", me)), wires[me])
        if telemetry:
            sock = SidecarSocket(
                sock,
                ProvenanceLog(f"peer{me}", pid=me, clock=lambda: net.now),
            )
        sock = ChaosSocket(
            sock, plan, clock=lambda: net.now, addr=("peer", me)
        )
        builder = (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(2)
            .with_max_prediction_window(8)
        )
        for h in range(2):
            builder.add_player(
                PlayerType.local() if h == me
                else PlayerType.remote(("peer", h)), h,
            )
        kw = {}
        if telemetry:
            kw = dict(
                metrics=Metrics(),
                tracer=SpanTracer(clock=lambda: net.now, pid=me),
            )
        session = builder.start_p2p_session(
            sock, clock=lambda: net.now, **kw
        )
        runner = RollbackRunner(
            box_game.make_schedule(), box_game.make_world(2).commit(),
            max_prediction=8, num_players=2,
            input_spec=box_game.INPUT_SPEC, **kw,
        )
        peers.append((session, runner))
    for _ in range(240):
        net.advance(FPS_DT)
        for i, (session, runner) in enumerate(peers):
            session.poll_remote_clients(parts=[0.0, 0.0] if split else None)
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
            history[i].update(session._local_checksums)
            if telemetry and i == 0:
                recorder.capture(session=session, runner=runner)
    assert all(s.current_frame >= 150 for s, _ in peers)
    final = [combine64(checksum(r.state)) for _, r in peers]
    return wires, history, final


class TestP2PInert:
    def test_full_stack_on_vs_off_is_bitwise_identical(self):
        on = run_p2p(telemetry=True)
        off = run_p2p(telemetry=False)
        # Same wire bytes, same order, both directions, both peers —
        # the sidecar transmitted nothing and moved no chaos RNG draw.
        assert on[0] == off[0]
        # Same per-frame state checksums and same final states.
        assert on[1] == off[1]
        assert on[2] == off[2]


    def test_poll_split_on_vs_off_is_bitwise_identical(self):
        """The caller's ``parts`` list only reads the clock: same wire
        bytes, per-frame checksums and final states with it and without."""
        assert run_p2p(telemetry=False, split=True) == run_p2p(
            telemetry=False)


class _CountingClock:
    """Stands in for ``time.perf_counter``: counts its reads."""

    def __init__(self):
        import time

        self.reads = 0
        self._real = time.perf_counter

    def __call__(self):
        self.reads += 1
        return self._real()


class TestNullSinksReadNoClock:
    """With the sinks null the served frame's new names cost nothing: no
    span object, no clock read a match, none in the session's poll."""

    def _pair(self):
        net = LoopbackNetwork()
        sessions = []
        for me in range(2):
            builder = (
                SessionBuilder(box_game.INPUT_SPEC).with_num_players(2)
            )
            for h in range(2):
                builder.add_player(
                    PlayerType.local() if h == me
                    else PlayerType.remote(("peer", h)), h,
                )
            sessions.append(builder.start_p2p_session(
                net.socket(("peer", me)), clock=lambda: net.now
            ))
        return net, sessions

    def test_poll_reads_the_clock_only_for_a_caller_that_asks(
        self, monkeypatch
    ):
        import time

        net, sessions = self._pair()
        clock = _CountingClock()
        monkeypatch.setattr(time, "perf_counter", clock)
        for _ in range(30):
            net.advance(FPS_DT)
            for s in sessions:
                s.poll_remote_clients()
        assert clock.reads == 0
        parts = [0.0, 0.0]
        net.advance(FPS_DT)
        sessions[0].poll_remote_clients(parts=parts)
        assert clock.reads == 4  # the two sides, a pair of reads each
        assert parts[0] > 0.0 and parts[1] > 0.0

    @pytest.mark.parametrize("sinks", [False, True])
    def test_per_match_loop_reads_no_clock_beyond_its_pair(
        self, sinks, monkeypatch
    ):
        """Reads of ``time.perf_counter`` and of the server's own clock
        over served frames, for 1 and for 3 matches in one group: with
        the sinks null the first does not grow with the matches and the
        second grows by the watchdog's pair a match; with a sink, a read
        at each boundary of a match's five kinds of work."""
        import gc
        import time

        from bevy_ggrs_tpu.obs import trace as obs_trace
        from tests.test_serve_faults import (
            inputs_for, make_server, make_synctest,
        )

        frames = 4

        def reads(matches):
            own = _CountingClock()
            srv = make_server(
                metrics=Metrics() if sinks else None, clock=own,
                capacity=4, stagger_groups=1,
            )
            for k in range(matches):
                srv.add_match(make_synctest(), inputs_for(k))
            srv.run_frame()
            spans = []
            real_init = obs_trace._Span.__init__

            def counted_init(span, *a, **kw):
                spans.append(a[2])
                real_init(span, *a, **kw)

            perf = _CountingClock()
            own.reads = 0
            gc.disable()  # a collection is a span too (``gc_pause``)
            try:
                with pytest.MonkeyPatch.context() as patch:
                    patch.setattr(time, "perf_counter", perf)
                    patch.setattr(obs_trace._Span, "__init__", counted_init)
                    for _ in range(frames):
                        srv.run_frame()
            finally:
                gc.enable()
            srv.close()
            return perf.reads, own.reads, spans

        perf_1, own_1, spans_1 = reads(1)
        perf_3, own_3, spans_3 = reads(3)
        assert own_3 - own_1 == 2 * 2 * frames
        if not sinks:
            assert spans_1 == spans_3 == []  # NULL_SPAN at every site
            assert perf_3 == perf_1
        else:
            assert len(spans_3) == len(spans_1)  # none a match
            assert {"serve_frame", "serve_segment", "serve_post",
                    "serve_report_delivery"} <= set(spans_3)
            # A SyncTest match has no poll: five boundaries, not six.
            assert perf_3 - perf_1 == 5 * 2 * frames


def run_p2p_spec(ledger_on: bool):
    """Same chaos pair, but peer 0 SPECULATES — the only variable is the
    speculation ledger, so a ledger that touched the wire, moved a chaos
    RNG draw, or perturbed the branch tree breaks the byte compare."""
    net = LoopbackNetwork()
    plan = ChaosPlan.generate(11, 3.0, (("peer", 0), ("peer", 1)))
    wires = {0: [], 1: []}
    history = [{}, {}]
    peers = []
    for me in range(2):
        sock = WireRecorder(net.socket(("peer", me)), wires[me])
        sock = ChaosSocket(
            sock, plan, clock=lambda: net.now, addr=("peer", me)
        )
        builder = (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(2)
            .with_max_prediction_window(8)
        )
        for h in range(2):
            builder.add_player(
                PlayerType.local() if h == me
                else PlayerType.remote(("peer", h)), h,
            )
        session = builder.start_p2p_session(sock, clock=lambda: net.now)
        if me == 0:
            runner = SpeculativeRollbackRunner(
                box_game.make_schedule(), box_game.make_world(2).commit(),
                max_prediction=8, num_players=2,
                input_spec=box_game.INPUT_SPEC,
                num_branches=16, spec_frames=8,
                ledger=SpeculationLedger() if ledger_on else None,
            )
        else:
            runner = RollbackRunner(
                box_game.make_schedule(), box_game.make_world(2).commit(),
                max_prediction=8, num_players=2,
                input_spec=box_game.INPUT_SPEC,
            )
        peers.append((session, runner))
    for _ in range(240):
        net.advance(FPS_DT)
        for i, (session, runner) in enumerate(peers):
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
            if isinstance(runner, SpeculativeRollbackRunner):
                runner.speculate(session.confirmed_frame(), session)
            history[i].update(session._local_checksums)
    assert all(s.current_frame >= 150 for s, _ in peers)
    r0 = peers[0][1]
    assert r0.rollbacks_total > 0 and r0.spec_hits + r0.spec_partial_hits > 0
    final = [combine64(checksum(r.state)) for _, r in peers]
    return wires, history, final


class TestLedgerInert:
    def test_ledger_on_vs_off_is_wire_bitwise_identical(self):
        on = run_p2p_spec(ledger_on=True)
        off = run_p2p_spec(ledger_on=False)
        assert on[0] == off[0]
        assert on[1] == off[1]
        assert on[2] == off[2]

    def test_batched_s8_ledger_on_vs_off_identical(self):
        def run(ledger_on):
            kw = (
                dict(ledger=SpeculationLedger()) if ledger_on else {}
            )
            core = make_core(num_slots=8, **kw)
            slots = [core.admit() for _ in range(8)]
            scripts = {
                s: make_script(seed=200 + s, depth=1 + (s % 4), cycles=2)
                for s in slots
            }
            drive(core, scripts)
            sums = {
                s: combine64(checksum(core.slot_state(s))) for s in slots
            }
            logs = {s: dict(core.slots[s].input_log) for s in slots}
            return sums, logs

        on_sums, on_logs = run(True)
        off_sums, off_logs = run(False)
        assert on_sums == off_sums
        for s in on_logs:
            for f in on_logs[s]:
                assert np.array_equal(on_logs[s][f], off_logs[s][f])


def run_batched(telemetry: bool, S=8):
    kw = {}
    if telemetry:
        kw = dict(metrics=Metrics(), tracer=SpanTracer())
    core = make_core(num_slots=S, **kw)
    slots = [core.admit() for _ in range(S)]
    scripts = {
        s: make_script(seed=200 + s, depth=1 + (s % 4), cycles=2)
        for s in slots
    }
    drive(core, scripts)
    sums = {s: combine64(checksum(core.slot_state(s))) for s in slots}
    logs = {s: dict(core.slots[s].input_log) for s in slots}
    return sums, logs


class TestBatchedInert:
    def test_s8_checksums_and_input_logs_identical(self):
        on_sums, on_logs = run_batched(telemetry=True)
        off_sums, off_logs = run_batched(telemetry=False)
        assert on_sums == off_sums
        assert on_logs.keys() == off_logs.keys()
        for s in on_logs:
            assert on_logs[s].keys() == off_logs[s].keys()
            for f in on_logs[s]:
                assert np.array_equal(on_logs[s][f], off_logs[s][f]), (
                    f"slot {s} frame {f} canonical input log diverged"
                )


@pytest.mark.slow
class TestEnabledOverhead:
    def test_enabled_path_overhead_within_5pct_of_frame_budget_s256(self):
        """Acceptance: the ENABLED telemetry path (spans + labeled
        metrics + speculation ledger) adds at most 5% of the 60 Hz frame
        budget per batched tick at S=256."""
        import time

        S, frame_ms = 256, 1000.0 / 60.0

        def timed(telemetry):
            kw = {}
            if telemetry:
                kw = dict(
                    metrics=Metrics(), tracer=SpanTracer(),
                    ledger=SpeculationLedger(),
                )
            core = make_core(num_slots=S, **kw)
            slots = [core.admit() for _ in range(S)]
            scripts = {
                s: make_script(seed=300 + s, depth=1 + (s % 4), cycles=3)
                for s in slots
            }
            ticks = max(len(v) for v in scripts.values())
            t0 = time.perf_counter()
            drive(core, scripts)
            return (time.perf_counter() - t0) * 1000.0 / ticks

        base = timed(False)
        # Warm both paths' executables before trusting the clock.
        timed(True)
        enabled = timed(True)
        overhead = enabled - base
        assert overhead <= 0.05 * frame_ms, (
            f"enabled telemetry adds {overhead:.3f} ms/tick at S={S} "
            f"(budget 5% of {frame_ms:.1f} ms = {0.05 * frame_ms:.3f} ms; "
            f"base {base:.3f} ms, enabled {enabled:.3f} ms)"
        )

    def test_profiler_on_overhead_within_5pct_of_frame_budget_s256(self):
        """Acceptance: running the span-aware sampling profiler at its
        default ~2 ms cadence against the serving thread adds at most 5%
        of the 60 Hz frame budget per batched tick at S=256. The sampled
        thread pays only brief GIL holds while the sampler walks its
        frames — the budget is the whole point of sampling over
        instrumenting."""
        import time

        from bevy_ggrs_tpu.obs.profiler import HostProfiler

        S, frame_ms = 256, 1000.0 / 60.0

        def timed(profiled):
            core = make_core(num_slots=S)
            slots = [core.admit() for _ in range(S)]
            scripts = {
                s: make_script(seed=300 + s, depth=1 + (s % 4), cycles=3)
                for s in slots
            }
            ticks = max(len(v) for v in scripts.values())
            prof = HostProfiler(seed=5) if profiled else None
            if prof is not None:
                prof.start()
            try:
                t0 = time.perf_counter()
                drive(core, scripts)
                per_tick = (time.perf_counter() - t0) * 1000.0 / ticks
            finally:
                if prof is not None:
                    prof.stop()
            if prof is not None:
                assert prof.samples > 0
            return per_tick

        base = timed(False)
        timed(True)  # warm before trusting the clock
        profiled = timed(True)
        overhead = profiled - base
        assert overhead <= 0.05 * frame_ms, (
            f"profiler adds {overhead:.3f} ms/tick at S={S} "
            f"(budget 5% of {frame_ms:.1f} ms = {0.05 * frame_ms:.3f} ms; "
            f"base {base:.3f} ms, profiled {profiled:.3f} ms)"
        )


# Defined AFTER the overhead classes: these runs allocate two full chaos
# P2P pairs and two batched cores, and the S=256 overhead timings above
# are only honest against the process state the committed baseline was
# measured in.
class TestProfilerInert:
    def test_profiler_on_vs_off_is_wire_bitwise_identical(self):
        """The sampling host profiler only READS interpreter state: a
        chaos-faulted P2P pair profiled at a hot 1 ms cadence must
        produce the same wire bytes, per-frame checksums, and final
        states as the identical unprofiled run."""
        from bevy_ggrs_tpu.obs.profiler import HostProfiler

        prof = HostProfiler(interval_ms=1.0, seed=7)
        prof.start()
        try:
            on = run_p2p(telemetry=True)
        finally:
            prof.stop()
        off = run_p2p(telemetry=True)
        assert prof.samples > 0  # the sampler actually ran
        assert on[0] == off[0]  # wire bytes, both peers, both directions
        assert on[1] == off[1]  # per-frame checksums
        assert on[2] == off[2]  # final states

    def test_profiler_on_batched_states_identical(self):
        from bevy_ggrs_tpu.obs.profiler import HostProfiler

        prof = HostProfiler(interval_ms=1.0, seed=7)
        prof.start()
        try:
            on_sums, on_logs = run_batched(telemetry=True)
        finally:
            prof.stop()
        off_sums, off_logs = run_batched(telemetry=True)
        assert on_sums == off_sums
        for s in on_logs:
            for f in on_logs[s]:
                assert np.array_equal(on_logs[s][f], off_logs[s][f])

"""The unit that crosses between a session and the batch core is the segment.

A session's frame is ONE ``[Load?, (Save, Advance)*]`` run; since PR 36 the
session builds it from a :class:`~bevy_ggrs_tpu.session.requests.Segment`
(the two arrays the queue set's ``advance`` returned) and the request list is
``Segment.requests()``. A hosting loop takes the segment itself, and the
checksums go back one ``report_checksums`` call a segment part. Pinned here,
on both planes (``plane``: the native core, and what ``GGRS_NO_NATIVE=1``
leaves, every factory's Python fallback):

(a) ``advance_segment().requests()`` is, element for element, the list the
    parent's ``advance_frame()`` built from the same ``advance`` result;
(b) ``BatchedSessionCore.tick`` fed segments and fed the equal lists leaves
    bitwise the same carry, states, input logs, ledger, counters and
    delivered checksums;
(c) a session-made segment that is not canonical faults as the equal list
    would, before anything of any slot changed;
(d) ``report_checksums(first, values)`` is n ``report_checksum`` calls;
(e) a watchdog strike and a ``SlotFault`` hand the recovery lane a request
    list, which it runs to the frame a never-faulted twin reaches.
"""

import jax
import numpy as np
import pytest

from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.native import core as ncore
from bevy_ggrs_tpu.obs.ledger import SpeculationLedger
from bevy_ggrs_tpu.schedule import PREDICTED, InputSpec
from bevy_ggrs_tpu.serve import SlotFault, SlotHealth
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore
from bevy_ggrs_tpu.serve.server import MatchServer
from bevy_ggrs_tpu.session import (
    MismatchedChecksum,
    PlayerType,
    PredictionThreshold,
    SessionBuilder,
    SessionState,
    SyncTestSession,
)
from bevy_ggrs_tpu.session.requests import (
    AdvanceFrame,
    LoadGameState,
    RestoreGameState,
    SaveGameState,
    Segment,
    SegmentError,
)
from bevy_ggrs_tpu.state import checksum, combine64
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_batched_sessions import (
    BRANCHES,
    MAXPRED,
    P,
    SPEC_FRAMES,
    make_script,
)

FPS_DT = 1.0 / 60.0
NULL_FRAME = ncore.NULL_FRAME


@pytest.fixture(params=["native", "python"])
def plane(request, monkeypatch):
    """Both planes: the native core, and every factory's Python fallback
    (what ``GGRS_NO_NATIVE=1`` selects: queue sets, trackers, tree builders
    and the batch plane all ask ``native.core.available()``)."""
    if request.param == "python":
        monkeypatch.setattr(ncore, "available", lambda: False)
    elif not ncore.available():
        pytest.skip("native session core did not build")
    return request.param


def canon(requests):
    """A request list as comparable values, arrays with dtype and bytes."""
    out = []
    for r in requests:
        if isinstance(r, AdvanceFrame):
            out.append((
                "AdvanceFrame", r.bits.dtype.str, r.bits.shape,
                r.bits.tobytes(), r.status.dtype.str, r.status.tobytes(),
            ))
        else:
            assert isinstance(r, (LoadGameState, SaveGameState)), r
            out.append((type(r).__name__, r.frame))
    return out


def record_advances(session):
    """Keep what every ``_qset.advance`` call of ``session`` returned."""
    seen = []
    inner = session._qset.advance

    def advance(*a, **kw):
        out = inner(*a, **kw)
        seen.append(out)
        return out

    session._qset.advance = advance
    return seen


def parents_list(load, start, bits, status):
    """The list the parent's ``advance_frame()`` built from one ``advance``
    result (``session/synctest.py`` / ``session/p2p.py`` at b49cdaf: a
    ``LoadGameState`` when a frame is loaded, then a (save, advance) pair a
    row)."""
    requests = [] if load is None else [LoadGameState(load)]
    for i in range(len(bits)):
        requests.append(SaveGameState(start + i))
        requests.append(AdvanceFrame(bits=bits[i], status=status[i]))
    return requests


# ---------------------------------------------------------------------------
# (a) the list is the segment's


@pytest.mark.parametrize("input_delay", [0, 2])
@pytest.mark.parametrize("check_distance", [0, 1, 2])
def test_synctest_segment_requests_are_the_parents_list(
    plane, check_distance, input_delay
):
    session = SyncTestSession(
        2, InputSpec(), check_distance, MAXPRED, input_delay
    )
    seen = record_advances(session)
    rng = np.random.RandomState(7 + check_distance)
    for frame in range(3 * MAXPRED):
        for h in range(2):
            session.add_local_input(h, np.uint8(rng.randint(16)))
        # Both entry points, turn about: one implementation.
        if frame % 2:
            got = session.advance_frame()
        else:
            seg = session.advance_segment()
            assert seg.status.dtype == np.int32
            assert seg.n == len(seg.bits) == len(seg.status)
            got = seg.requests()
        start, _load, bits, status = seen[-1][:4]
        # The parent loaded ``start`` once history allowed the forced
        # rollback (before and after that point are both in the run).
        resim = check_distance > 0 and frame >= check_distance
        want = parents_list(start if resim else None, start, bits, status)
        assert canon(got) == canon(want), frame
        assert len(got) == (1 + 2 * (check_distance + 1) if resim else 2)
    assert session.current_frame == 3 * MAXPRED


def p2p_pair(net, spectators=(), max_prediction=8):
    sessions = []
    for me in range(2):
        builder = (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(2)
            .with_max_prediction_window(max_prediction)
        )
        for h in range(2):
            builder.add_player(
                PlayerType.local() if h == me
                else PlayerType.remote(("peer", h)), h,
            )
        if me == 0:
            for addr in spectators:
                builder.add_player(PlayerType.spectator(addr), 3)
        sessions.append(builder.start_p2p_session(
            net.socket(("peer", me)), clock=lambda: net.now,
        ))
    return sessions


def drive_p2p(net, sessions, seen, inputs, frames, extra=()):
    """Tick both peers ``frames`` times; returns the segments they made,
    each checked against the parent's list."""
    segments = []
    for i in range(frames):
        net.advance(FPS_DT)
        for other in extra:
            other.poll_remote_clients()
        for me, s in enumerate(sessions):
            s.poll_remote_clients()
            if s.current_state() != SessionState.RUNNING:
                continue
            s.add_local_input(me, inputs(me, s.current_frame))
            calls = len(seen[me])
            try:
                if (i + me) % 2:
                    seg, got = None, s.advance_frame()
                else:
                    seg = s.advance_segment()
                    got = seg.requests()
            except PredictionThreshold:
                continue
            assert len(seen[me]) == calls + 1
            start, load, bits, status = seen[me][-1][:4]
            want = parents_list(
                None if load == NULL_FRAME else load, start, bits, status
            )
            assert canon(got) == canon(want), (me, i)
            if seg is not None:
                segments.append(seg)
    return segments


def scripted(handle, frame):
    keys = [box_game.INPUT_UP, box_game.INPUT_RIGHT, box_game.INPUT_DOWN, 0]
    return np.uint8(keys[(frame // 3 + handle) % len(keys)])


@pytest.mark.parametrize(
    "case", ["no_rollback", "rollback", "clamped_load", "spectator"]
)
def test_p2p_segment_requests_are_the_parents_list(plane, case):
    lossy = case in ("rollback", "spectator")
    net = LoopbackNetwork(
        latency=2 * FPS_DT if lossy else 0.0,
        jitter=FPS_DT if lossy else 0.0, loss=0.03 if lossy else 0.0, seed=5,
    )
    spectators = [("spec", 0)] if case == "spectator" else []
    sessions = p2p_pair(net, spectators)
    extra = []
    if spectators:
        extra.append(
            SessionBuilder(box_game.INPUT_SPEC).with_num_players(2)
            .start_spectator_session(
                ("peer", 0), net.socket(spectators[0]),
                clock=lambda: net.now,
            )
        )
    seen = [record_advances(s) for s in sessions]
    # A held input equal to the first prediction (zero) is always
    # predicted right: nothing to roll back.
    inputs = (
        (lambda h, f: np.uint8(0)) if case in ("no_rollback", "clamped_load")
        else scripted
    )
    segments = drive_p2p(net, sessions, seen, inputs, 120, extra)
    assert len(segments) > 20
    loads = [seg.load for seg in segments if seg.load is not None]
    if case == "no_rollback":
        assert not loads and all(seg.n == 1 for seg in segments)
    if lossy:
        assert loads and all(
            seg.start == seg.load and seg.n > 1
            for seg in segments if seg.load is not None
        )
    if case == "spectator":
        # The fan-out ran inside advance_segment(): the spectator follows.
        assert sessions[0]._spec_sent[("spec", 0)] > 0
    if case == "clamped_load":
        # A late input contradicts a frame settled deeper than the window
        # reaches: the load is clamped to frame - max_prediction.
        s = sessions[0]
        frame = s.current_frame
        old = frame - 8 - 3
        bits, status = s._qset.gather(old, None)
        status = status.copy()
        status[1] = PREDICTED
        s._tracker.record_used(old, bits, status)
        s._tracker.note_confirmed(1, old, np.uint8(9))
        assert s._tracker.first_incorrect == old
        s.add_local_input(0, np.uint8(0))
        seg = s.advance_segment()
        assert (seg.load, seg.start, seg.n) == (frame - 8, frame - 8, 9)
        start, load, bits, status = seen[0][-1][:4]
        assert canon(seg.requests()) == canon(
            parents_list(load, start, bits, status)
        )


def test_from_requests_round_trips_and_names_what_it_cannot_say():
    seg = Segment(
        4, 4, np.arange(6, dtype=np.uint8).reshape(3, 2),
        np.ones((3, 2), np.int32),
    )
    (back,) = Segment.from_requests(seg.requests())
    assert (back.load, back.start) == (4, 4)
    assert canon(back.requests()) == canon(seg.requests())
    # Two Loads: two segments, in order; no request: none.
    two = Segment.from_requests(seg.requests() + seg.requests())
    assert [s.n for s in two] == [3, 3]
    assert Segment.from_requests([]) == []
    for bad, reason in [
        ([RestoreGameState(0, None)], "restore_request"),
        ([object()], "unsupported_request"),
        ([SaveGameState(0)], "non_canonical_burst"),
        (seg.requests()[2:3], "non_canonical_burst"),  # an advance alone
        ([SaveGameState(0), seg.requests()[2], SaveGameState(2),
          seg.requests()[2]], "non_canonical_burst"),  # labels skip one
    ]:
        with pytest.raises(SegmentError) as ei:
            Segment.from_requests(bad)
        assert ei.value.reason == reason


# ---------------------------------------------------------------------------
# (b) tick fed segments == tick fed lists


class Log:
    """A user's own session: ``report_checksum`` alone (the per-row path)."""

    def __init__(self):
        self.seen = {}
        self.calls = 0

    def report_checksum(self, frame, cs):
        self.calls += 1
        self.seen[frame] = int(cs)


class BulkLog(Log):
    """The same, taking a segment part at a time."""

    rows = 0

    def report_checksums(self, first, values):
        self.calls += 1
        self.rows += len(values)
        for i, cs in enumerate(values):
            self.seen[first + i] = int(cs)


def make_core(**kw):
    core = BatchedSessionCore(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        MAXPRED, P, box_game.INPUT_SPEC, num_slots=3,
        num_branches=BRANCHES, spec_frames=SPEC_FRAMES, **kw,
    )
    core.warmup()
    return core


def carry_bytes(core):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(
        (core._carry, core.states)
    )]


def host_state(core):
    return [
        (s.frame, s.res_anchor,
         None if s.res_bits is None else np.asarray(s.res_bits).tobytes(),
         sorted((f, np.asarray(b).tobytes()) for f, b in s.input_log.items()))
        for s in core.slots
    ]


def ledger_entries(ledger):
    return [
        {k: v for k, v in e.items() if k != "ts_us"}
        for e in ledger.entries
    ]


def test_tick_fed_segments_equals_tick_fed_lists(plane):
    scripts = {
        0: make_script(seed=21, depth=2, cycles=3),
        1: make_script(seed=22, depth=MAXPRED, cycles=2),
    }
    cores, logs, metrics, ledgers = [], [], [], []
    for _ in range(2):
        metrics.append(Metrics())
        ledgers.append(SpeculationLedger())
        cores.append(make_core(metrics=metrics[-1], ledger=ledgers[-1]))
        assert (cores[-1]._plane is not None) == (plane == "native")
        logs.append({0: BulkLog(), 1: Log()})
        for _slot in scripts:
            cores[-1].admit()
    as_lists, as_segments = cores
    for t in range(max(map(len, scripts.values()))):
        work = {
            slot: (script[t][0], script[t][1])
            for slot, script in scripts.items() if t < len(script)
        }
        as_lists.tick({
            slot: (reqs, conf, logs[0][slot])
            for slot, (reqs, conf) in work.items()
        })
        as_segments.tick({
            slot: (Segment.from_requests(reqs)[0], conf, logs[1][slot])
            for slot, (reqs, conf) in work.items()
        })
        assert carry_bytes(as_lists) == carry_bytes(as_segments), t
        assert host_state(as_lists) == host_state(as_segments), t
    for core in cores:
        core.flush_reports()
    assert ledger_entries(ledgers[0]) == ledger_entries(ledgers[1])
    assert len(ledgers[0].entries) == 5  # one a rollback
    for slot in scripts:
        assert logs[0][slot].seen == logs[1][slot].seen
        assert len(logs[0][slot].seen) == len(scripts[slot])
        assert logs[0][slot].calls == logs[1][slot].calls
    # A segment part is one call for a session that takes it so, a call a
    # row for one that does not.
    assert logs[0][0].calls < logs[0][0].rows
    assert logs[0][1].calls >= len(logs[0][1].seen)
    for m in metrics:
        del m.series["serve_segment_direct_share"][:]  # what differs
    assert metrics[0].counters == metrics[1].counters
    assert metrics[0].counters["frames_advanced"] == sum(
        sum(isinstance(r, AdvanceFrame) for r in reqs)
        for script in scripts.values() for reqs, _ in script
    )
    assert {
        k: v for k, v in metrics[0].series.items() if not k.endswith("_ms")
    } == {k: v for k, v in metrics[1].series.items() if not k.endswith("_ms")}


def test_direct_share_counts_session_made_segments(plane):
    metrics = Metrics()
    core = make_core(metrics=metrics)
    a, b = core.admit(), core.admit()
    sessions = {
        s: SyncTestSession(P, box_game.INPUT_SPEC, 2, MAXPRED) for s in (a, b)
    }
    for frame in range(6):
        work = {}
        for slot, session in sessions.items():
            for h in range(P):
                session.add_local_input(h, np.uint8((frame + h + slot) % 16))
            # Slot ``b`` hands over the equal list from frame 3 on.
            item = (
                session.advance_frame() if slot == b and frame >= 3
                else session.advance_segment()
            )
            work[slot] = (item, None, session)
        core.tick(work)
    core.flush_reports()  # every frame compared: no MismatchedChecksum
    assert metrics.series["serve_segment_direct_share"] == [1.0] * 3 + [0.5] * 3
    assert metrics.series["serve_rounds"] == [1] * 6
    assert [s.frame for s in core.slots[:2]] == [6, 6]
    assert all(len(s._checksums) >= 3 for s in sessions.values())


# ---------------------------------------------------------------------------
# (c) fault atomicity for a session-made segment


def test_a_bad_segment_faults_as_its_list_before_anything_changed(plane):
    core = make_core(metrics=Metrics())
    a, b = core.admit(), core.admit()
    sa = make_script(seed=31, depth=2, cycles=1)
    sb = make_script(seed=32, depth=3, cycles=1)
    for t in range(3):
        core.tick({a: sa[t] + (None,), b: sb[t] + (None,)})
    frame = core.slots[b].frame
    bits = lambda n: np.ones((n, P), np.uint8)  # noqa: E731
    status = lambda n: np.zeros((n, P), np.int32)  # noqa: E731
    too_many = core.burst_frames + 1
    bad = {
        "wrong_start": Segment(None, frame + 1, bits(1), status(1)),
        "wrong_start_after_load":
            Segment(frame - 1, frame, bits(1), status(1)),
        "no_frames": Segment(frame - 1, frame - 1, bits(0), status(0)),
        "too_many": Segment(None, frame, bits(too_many), status(too_many)),
    }
    reasons = {
        "wrong_start": "non_canonical_burst",
        "wrong_start_after_load": "non_canonical_burst",
        "no_frames": "non_canonical_burst",
        "too_many": "burst_overflow",
    }
    good = Segment.from_requests(sa[3][0])[0]
    before = (carry_bytes(core), host_state(core), dict(core.metrics.counters))
    for name, seg in bad.items():
        for item in (seg, seg.requests()):
            with pytest.raises(SlotFault) as ei:
                # The good slot comes first: its segment was checked, and
                # nothing of it may have been applied.
                core.tick({a: (good, sa[3][1], None), b: (item, None, None)})
            assert (ei.value.slot, ei.value.reason, ei.value.frame) == (
                b, reasons[name], frame), name
            after = (
                carry_bytes(core), host_state(core),
                dict(core.metrics.counters),
            )
            assert after == before, name
    with pytest.raises(SlotFault) as ei:
        core.tick({
            a: (good, sa[3][1], None),
            b: ([SaveGameState(frame), RestoreGameState(0, None)], None, None),
        })
    assert (ei.value.slot, ei.value.reason) == (b, "restore_request")
    assert isinstance(ei.value.cause, SegmentError)
    assert (carry_bytes(core), host_state(core)) == before[:2]
    # The survivor's same work still runs, bitwise a core that never saw
    # the faults.
    twin = make_core()
    ta, tb = twin.admit(), twin.admit()
    for t in range(3):
        twin.tick({ta: sa[t] + (None,), tb: sb[t] + (None,)})
    core.tick({a: (good, sa[3][1], None)})
    twin.tick({ta: sa[3] + (None,)})
    assert carry_bytes(core) == carry_bytes(twin)
    assert host_state(core) == host_state(twin)


# ---------------------------------------------------------------------------
# (d) report_checksums is n report_checksum calls


def test_synctest_report_checksums_is_n_report_checksum_calls(plane):
    one, many = (
        SyncTestSession(2, InputSpec(), 2, MAXPRED) for _ in range(2)
    )
    values = [(f * 2654435761) & 0xFFFFFFFFFFFF for f in range(12)]
    for f, v in enumerate(values[:8]):
        one.report_checksum(f, v)
    many.report_checksums(0, values[:8])
    assert one._checksums == many._checksums
    # The same values again compare equal; NumPy integers are taken too.
    many.report_checksums(3, np.asarray(values[3:8], np.uint64))
    assert one._checksums == many._checksums
    assert all(type(v) is int for v in many._checksums.values())
    # A tampered frame in the middle: the frames before it are stored, it
    # raises with the same fields, and nothing after it is looked at.
    tampered = list(values[6:12])
    tampered[1] ^= 1  # frame 7, known
    tampered[4] ^= 1  # frame 10, would be new
    errors = []
    for session, report in (
        (one, lambda: [
            one.report_checksum(6 + i, v) for i, v in enumerate(tampered)
        ]),
        (many, lambda: many.report_checksums(6, tampered)),
    ):
        with pytest.raises(MismatchedChecksum) as ei:
            report()
        errors.append(
            (ei.value.frame, ei.value.original, ei.value.resimulated)
        )
    assert errors[0] == errors[1] == (7, values[7], values[7] ^ 1)
    assert one._checksums == many._checksums
    assert 10 not in many._checksums


def test_p2p_report_checksums_is_the_wanted_report_checksum_calls(plane):
    net = LoopbackNetwork(seed=1)
    one, many = p2p_pair(net)
    interval = one.desync_interval
    assert interval > 1 and many.desync_interval == interval
    for s in (one, many):
        s._confirmed = 40 * interval  # the frontier the prune reads
        s._local_checksums.update(
            {f * interval: f for f in range(30, 40)}
        )
    horizon = 36 * interval
    first = 40 * interval - 3
    values = list(range(1000, 1000 + interval + 6))
    for i, v in enumerate(values):  # the driver's loop: wanted rows only
        if one.wants_checksum(first + i):
            one.report_checksum(first + i, v)
    many.report_checksums(first, np.asarray(values, np.uint64))
    assert one._local_checksums == many._local_checksums
    assert set(many._local_checksums) == {
        f * interval for f in range(36, 42)
    }
    assert many._local_checksums[40 * interval] == values[3]
    assert min(many._local_checksums) == horizon
    # No wanted frame in the run: nothing stored and nothing pruned, as
    # the driver's loop makes no call at all.
    for s in (one, many):
        s._local_checksums[0] = 5
    many.report_checksums(41 * interval + 1, values[: interval - 1])
    assert one._local_checksums == many._local_checksums
    assert many._local_checksums[0] == 5


# ---------------------------------------------------------------------------
# (e) what the recovery lane is handed


def make_server(**kw):
    server = MatchServer(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        MAXPRED, P, box_game.INPUT_SPEC, capacity=4, stagger_groups=2,
        num_branches=BRANCHES, spec_frames=SPEC_FRAMES, **kw,
    )
    server.warmup()
    return server


def inputs_for(seed):
    return lambda frame, handle: np.uint8((frame * 3 + handle * 5 + seed) % 16)


class SlowSession(SyncTestSession):
    """A SyncTest session whose ``advance_segment`` burns fake-clock time on
    some frames: hung as the server sees it, and still asked for segments
    (the override is the class's)."""

    def __init__(self, clk, hang_frames):
        super().__init__(P, box_game.INPUT_SPEC, 2, MAXPRED)
        self._clk, self._hang = clk, set(hang_frames)

    def advance_segment(self):
        if self.current_frame in self._hang:
            self._clk[0] += 0.2
        return super().advance_segment()


def synctest():
    return SyncTestSession(P, box_game.INPUT_SPEC, 2, MAXPRED)


def spy_on_lanes(server, monkeypatch):
    """The ``pending`` every recovery lane of ``server`` was built with."""
    from bevy_ggrs_tpu.serve import server as server_mod

    handed = []
    real = server_mod.RecoveryLane

    def lane(*a, **kw):
        handed.append(kw["pending"])
        return real(*a, **kw)

    monkeypatch.setattr(server_mod, "RecoveryLane", lane)
    return handed


def slot_checksums(server, handles):
    return {
        h: (
            server.groups[h.group].slots[h.slot].frame,
            combine64(checksum(server.groups[h.group].slot_state(h.slot))),
        )
        for h in handles
    }


@pytest.mark.parametrize("fault", ["watchdog_timeout", "burst_overflow"])
def test_the_lane_is_handed_the_segments_request_list(
    plane, fault, monkeypatch
):
    clk = [0.0]
    metrics = Metrics()
    server = make_server(
        metrics=metrics, clock=lambda: clk[0], watchdog_budget_ms=50.0,
        watchdog_strike_limit=2,
    )
    control = make_server()
    handed = spy_on_lanes(server, monkeypatch)
    hang = {4, 5} if fault == "watchdog_timeout" else ()
    sick = server.add_match(SlowSession(clk, hang), inputs_for(3))
    handles = [sick, server.add_match(synctest(), inputs_for(4))]
    c_handles = [
        control.add_match(synctest(), inputs_for(3)),
        control.add_match(synctest(), inputs_for(4)),
    ]
    if fault == "burst_overflow":
        # The batch refuses slot ``sick``'s (canonical) segment once, at
        # frame 5: what tick() raises for a shape it cannot run.
        core = server.groups[sick.group]
        check = core._check_segment

        def refuse_once(slot, frame, seg):
            if slot == sick.slot and frame == 5 and not handed:
                raise SlotFault(slot, "burst_overflow", frame)
            return check(slot, frame, seg)

        core._check_segment = refuse_once
    for _ in range(12):
        server.run_frame()
        control.run_frame()
    # The lane replays the pending list AND takes its own step in the frame
    # of the fault, so the recovered match ends one frame ahead of its
    # never-faulted twin: the twin's state is read at both frames.
    twin = {12: slot_checksums(control, c_handles)}
    control.run_frame()
    twin[13] = slot_checksums(control, c_handles)
    assert server.faults_total == 1 and server.readmissions_total == 1
    m = server._matches[sick]
    assert m.fsm.state is SlotHealth.HEALTHY
    assert m.fsm.last_reason == fault
    # The lane got the frame's REQUEST LIST (the runner's vocabulary), the
    # segment's, with the session that made it.
    (pending,) = handed
    requests, session = pending
    assert session is m.session
    assert [type(r) for r in requests] == (
        [LoadGameState] + [SaveGameState, AdvanceFrame] * 3
    )
    assert requests[0].frame == 3 and requests[-2].frame == 5
    assert canon(requests) == canon(
        Segment.from_requests(requests)[0].requests()
    )
    # Nothing was lost on the way: every match, the faulted one included,
    # is bitwise a server's that never faulted, at the frame it reached.
    ours = slot_checksums(server, handles)
    assert [ours[h][0] for h in handles] == [13, 12]
    for h, c in zip(handles, c_handles):
        assert ours[h] == twin[ours[h][0]][c]
        assert server._matches[h].session.current_frame == ours[h][0]
    # Every work item of every group tick came as a session-made segment.
    assert set(metrics.series["serve_segment_direct_share"]) == {1.0}

"""A hosted match ends while its server serves: what the sessions report
reaches the operator (``MatchServer.drain_events``), a silent peer is
reported on the sessions' clock, and a retirement and an admission inside
served frames leave every other lane alone.

All on the CPU at capacity 4 (2 stagger groups), over the loopback
transport on a virtual clock that moves 1/60 s a served frame. A far end is
a ``P2PSession`` that feeds its player's inputs and discards its requests,
as the benchmark's (``benchmark/drivers/match_server_p2p.py`` ``FarEnd``).
"""

import numpy as np
import pytest

from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.schedule import CONFIRMED
from bevy_ggrs_tpu.serve import MatchServer
from bevy_ggrs_tpu.serve.admission import AdmissionTrace
from bevy_ggrs_tpu.session import (
    PlayerType,
    PredictionThreshold,
    SessionBuilder,
    SessionState,
)
from bevy_ggrs_tpu.session.common import EventKind
from bevy_ggrs_tpu.session.endpoint import PeerState
from bevy_ggrs_tpu.session.requests import AdvanceFrame, SaveGameState
from bevy_ggrs_tpu.state import ring_load
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from bevy_ggrs_tpu.utils import xla_cache
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_p2p import FPS_DT, scripted_input
from tests.test_serve_hosted_p2p import _tree_equal as tree_equal

MAX_PRED = 8
NOTIFY_FRAMES, TIMEOUT_FRAMES = 30, 120     # 0.5 s and 2.0 s at 60 Hz


def inputs_of(match):
    """A match's inputs: the scripted pattern, shifted by the match."""
    return lambda frame, handle: scripted_input(handle, frame + 5 * match)


class Hosted:
    """One MatchServer on a virtual clock, its matches by id, and their far
    ends; ``serve()`` is one served frame of a closed loop."""

    def __init__(self, matches, metrics=None, latency=1.0):
        self.net = LoopbackNetwork(latency=latency * FPS_DT)
        self.metrics = metrics
        self.server = MatchServer(
            box_game.make_schedule(), box_game.make_world(2).commit(),
            MAX_PRED, 2, box_game.INPUT_SPEC, capacity=4, stagger_groups=2,
            num_branches=8, spec_frames=3, metrics=metrics,
        )
        self.server.warmup()
        self.hosts, self.far, self.handles = {}, {}, {}
        for m in matches:
            self.handles[m] = self.server.add_match(
                self.session(0, m), inputs_of(m))
            self.far[m] = self.session(1, m)
        self.events = []                    # (served frame, handle, event)
        self.drained = True

    def session(self, me, m):
        names = ("srv", "ext")
        builder = (SessionBuilder(box_game.INPUT_SPEC).with_num_players(2)
                   .with_max_prediction_window(MAX_PRED))
        for h in range(2):
            builder.add_player(PlayerType.local() if h == me
                               else PlayerType.remote((names[h], m)), h)
        session = builder.start_p2p_session(
            self.net.socket((names[me], m)), clock=lambda: self.net.now)
        if me == 0:
            self.hosts[m] = session
        return session

    def serve(self, frames=1):
        for _ in range(frames):
            self.net.advance(FPS_DT)
            for m, far in self.far.items():
                far.poll_remote_clients()
                far.events()
                if far.current_state() != SessionState.RUNNING:
                    continue
                far.add_local_input(1, inputs_of(m)(far.current_frame, 1))
                try:
                    far.advance_frame()
                except PredictionThreshold:
                    pass
            self.server.run_frame()
            if self.drained:
                frame = self.server.frames_served - 1
                self.events += [(frame, h, ev)
                                for h, ev in self.server.drain_events()]

    def until_running(self, matches, limit=400):
        for _ in range(limit):
            if all(self.hosts[m].current_state() == SessionState.RUNNING
                   and self.far[m].current_state() == SessionState.RUNNING
                   for m in matches):
                return
            self.serve()
        raise AssertionError("sessions still synchronising")

    def silence(self, m):
        """The player's machine is gone: it sends and hears nothing."""
        self.far.pop(m).socket.close()

    def kinds(self, handle, *kinds):
        return [(f, ev) for f, h, ev in self.events
                if h is handle and ev.kind in kinds]


@pytest.fixture(scope="module")
def dropped():
    """Two hosted matches; match 1's player drops without a word once both
    play, and the server is served until it has reported the drop."""
    d = Hosted([0, 1], metrics=Metrics())
    d.until_running([0, 1])
    d.serve(20)
    d.silence(1)
    d.silenced_at = d.server.frames_served
    d.withheld_before = d.server.frames_withheld_total
    d.serve(TIMEOUT_FRAMES + 8)
    return d


def test_a_silent_peer_is_reported_on_the_sessions_clock(dropped):
    d, handle = dropped, dropped.handles[1]
    (interrupted, ev), = d.kinds(handle, EventKind.NETWORK_INTERRUPTED)
    assert ev.addr == ("ext", 1) and ev.data["disconnect_timeout"] == 2.0
    (gone, ev), = d.kinds(handle, EventKind.DISCONNECTED)
    assert ev.addr == ("ext", 1)
    # What was in flight lands within two frames of the silence; the poll
    # that notices and the supervisor's tick take a frame each.
    assert NOTIFY_FRAMES <= interrupted - d.silenced_at <= NOTIFY_FRAMES + 4
    assert TIMEOUT_FRAMES <= gone - d.silenced_at <= TIMEOUT_FRAMES + 4
    # In between the match predicted its window and then withheld.
    withheld = d.server.frames_withheld_total - d.withheld_before
    assert TIMEOUT_FRAMES - MAX_PRED - 4 <= withheld <= TIMEOUT_FRAMES
    stalled = d.server.slot_frames_stalled_total
    assert gone - interrupted - 2 <= stalled <= gone - interrupted
    assert d.metrics.series["disconnect_wait_frames"] == [gone - interrupted]
    # The other match heard its peer all along.
    assert not d.kinds(d.handles[0], EventKind.NETWORK_INTERRUPTED,
                       EventKind.DISCONNECTED)


def test_every_event_is_handed_out_once_with_its_handle(dropped):
    d = dropped
    assert {h for _, h, _ in d.events} == set(d.handles.values())
    for m, handle in d.handles.items():
        # The handshake's progress, as the session counted it: each once.
        counts = [ev.data["count"] for _, ev in
                  d.kinds(handle, EventKind.SYNCHRONIZING)]
        assert counts == sorted(set(counts)) and len(counts) == 4
        assert len(d.kinds(handle, EventKind.SYNCHRONIZED)) == 1
        assert all(ev.addr == ("ext", m) for _, h, ev in d.events
                   if h is handle and ev.addr is not None)
    # Advice to the loop that drives a session is the server's to take.
    assert all(ev.kind is not EventKind.WAIT_RECOMMENDATION
               for _, _, ev in d.events)
    assert d.server.match_events_delivered_total == len(d.events)
    assert d.metrics.counters["match_events_delivered"] == len(d.events)
    assert d.server.drain_events() == []


def test_a_server_nobody_drains_holds_one_served_frame_of_events():
    d = Hosted([0])
    d.drained = False
    d.until_running([0])
    # Five events of the handshake went by in as many served frames.
    kept = d.server.drain_events()
    assert sum(ev.kind in (EventKind.SYNCHRONIZING, EventKind.SYNCHRONIZED)
               for _, ev in kept) <= 1
    d.serve(3)
    d.silence(0)
    d.serve(TIMEOUT_FRAMES + 8)
    assert all(ev.kind is not EventKind.NETWORK_INTERRUPTED
               for _, ev in d.server.drain_events())
    assert d.server.match_events_delivered_total == len(kept)


def test_the_supervisor_rearms_a_dead_peer_and_the_retirement_ends_it(dropped):
    d, handle = dropped, dropped.handles[1]
    server, host = d.server, d.hosts[1]
    # The server's own supervisor (reconnect on) answered DISCONNECTED by
    # putting a fresh endpoint on the dead address ...
    assert d.metrics.counters["reconnects_initiated"] == 1
    endpoint = host._endpoints[("ext", 1)]
    assert endpoint.reconnecting and endpoint.state == PeerState.SYNCHRONIZING
    # ... and the match plays on with the player's inputs frozen, its slot
    # taken, until the operator lets go of it.
    frame = server.groups[handle.group].slots[handle.slot].frame
    d.serve(2)
    assert server.groups[handle.group].slots[handle.slot].frame == frame + 2
    server.retire_match(handle)
    slot = server.groups[handle.group].slots[handle.slot]
    assert not slot.active and slot.native is None
    assert handle not in server._matches and tuple(handle) not in server._at
    assert server.slots_free == 3 and server.matches_retired_total == 1
    # Nobody polls the session again: the re-armed endpoint's handshake
    # never goes on, and the slot's next frames build and fault nothing.
    sent = endpoint._last_sync_sent
    d.serve(40)
    assert endpoint._last_sync_sent == sent
    assert endpoint.state == PeerState.SYNCHRONIZING
    assert server.faults_total == 0 and server.slots_active == 1
    server.retire_match(handle)             # retired already: nothing
    assert server.matches_retired_total == 1


def replay(match, frames):
    """A serial singleton's state after ``frames`` frames of the match's
    inputs, every one confirmed."""
    oracle = RollbackRunner(
        box_game.make_schedule(), box_game.make_world(2).commit(),
        MAX_PRED, 2, box_game.INPUT_SPEC)
    status = np.full((2,), CONFIRMED, np.int32)
    feed = inputs_of(match)
    for f in range(frames):
        oracle.handle_requests([SaveGameState(f), AdvanceFrame(
            bits=np.asarray([feed(f, 0), feed(f, 1)], np.uint8),
            status=status)])
    return oracle.state


@pytest.mark.parametrize("end", ["game_over", "drop"])
def test_a_retirement_and_an_admission_leave_the_other_lanes_alone(end):
    """Match 3 ends (a game-over both ends know, or a drop the server
    reports) and match 4 is enqueued for its slot the next served frame,
    beside three lanes that play on: those are bitwise what a server
    without the turnover gives, nothing is compiled, the successor's
    rows hold nothing of the predecessor, and its confirmed frames are a
    fresh serial replay from ITS frame 0."""
    plain, turned = Hosted([0, 1, 2, 3]), Hosted([0, 1, 2, 3], Metrics())
    for d in (plain, turned):
        d.until_running([0, 1, 2, 3])
    assert plain.server.frames_served == turned.server.frames_served
    for d in (plain, turned):
        d.serve(30)
    compiles = xla_cache.compile_counters()["backend_compiles"]
    leaving = turned.handles[3]
    place = tuple(leaving)
    if end == "drop":
        turned.silence(3)
        for d in (plain, turned):
            d.serve(TIMEOUT_FRAMES + 4)
        assert turned.kinds(leaving, EventKind.DISCONNECTED)
    else:
        turned.silence(3)
    turned.server.retire_match(leaving)
    turned.hosts.pop(3).socket.close()
    for d in (plain, turned):
        d.serve()                       # the slot stands empty one frame
    trace = AdmissionTrace(4)
    turned.far[4] = turned.session(1, 4)
    turned.handles[4] = turned.server.enqueue_match(
        turned.session(0, 4), inputs_of(4), trace=trace)
    assert tuple(turned.handles[4]) == place
    for d in (plain, turned):
        d.serve(60)
    assert turned.hosts[4].current_state() == SessionState.RUNNING
    assert xla_cache.compile_counters()["backend_compiles"] == compiles
    assert turned.server.faults_total == 0
    for m in (0, 1, 2):
        a, b = plain.handles[m], turned.handles[m]
        assert tuple(a) == tuple(b)
        core_a, core_b = plain.server.groups[a.group], turned.server.groups[b.group]
        assert core_a.slots[a.slot].frame == core_b.slots[b.slot].frame
        assert tree_equal(core_a.slot_state(a.slot), core_b.slot_state(b.slot))
        assert tree_equal(core_a.slot_ring(a.slot), core_b.slot_ring(b.slot))
    # The successor: admitted once, counted once, its handshake in frames.
    server, host, h = turned.server, turned.hosts[4], turned.handles[4]
    assert server.admissions_completed == 5 and trace.complete is False
    assert set(trace.durations) == {"slot_warm", "admit", "first_frame"}
    series = turned.metrics.series
    assert len(series["admission_ms"]) == len(series["sync_frames"]) == 1
    assert len(series["admission_admit_ms"]) == 1
    assert 8 <= series["sync_frames"][0] <= 60
    assert server.slot_frames_syncing_total >= series["sync_frames"][0] - 1
    ring = server.groups[h.group].slot_ring(h.slot)
    labels = np.asarray(ring.frames).tolist()
    current = host.current_frame
    n = min(host.confirmed_frame() + 1, current - 1)
    assert n > 8 and labels[n % len(labels)] == n
    assert all(current - len(labels) <= f < current for f in labels)
    assert tree_equal(ring_load(ring, n), replay(4, n))

"""SpectatorSession e2e: host P2P pair + spectator, over loopback.

Reference behavior being replicated: spectators receive confirmed inputs
from a host, never contribute input, never roll back
(`/root/reference/src/ggrs_stage.rs:195-211`,
`examples/box_game/box_game_spectator.rs`).
"""

import numpy as np

from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.session import (
    PredictionThreshold,
    PlayerType,
    SessionBuilder,
    SessionState,
)
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from tests.test_p2p import FPS_DT, drive, make_pair, scripted_input


def make_spectator(net, host_addr, num_players=2):
    sock = net.socket(("spec", 0))
    session = (
        SessionBuilder(box_game.INPUT_SPEC)
        .with_num_players(num_players)
        .start_spectator_session(host_addr, sock, clock=lambda: net.now)
    )
    runner = RollbackRunner(
        box_game.make_schedule(),
        box_game.make_world(num_players).commit(),
        max_prediction=8,
        num_players=num_players,
        input_spec=box_game.INPUT_SPEC,
    )
    return session, runner


def drive_spectator(session, runner):
    session.poll_remote_clients()
    if session.current_state() != SessionState.RUNNING:
        return
    try:
        requests = session.advance_frame()
    except PredictionThreshold:
        return
    runner.handle_requests(requests, session)


class TestSpectator:
    def test_spectator_follows_host(self):
        net = LoopbackNetwork()
        peers = make_pair(net, spectators=[("spec", 0)])
        spec_session, spec_runner = make_spectator(net, ("peer", 0))

        for _ in range(120):
            net.advance(FPS_DT)
            for session, runner in peers:
                session.poll_remote_clients()
                if session.current_state() != SessionState.RUNNING:
                    continue
                for h in session.local_player_handles():
                    session.add_local_input(h, scripted_input(h, session.current_frame))
                try:
                    runner.handle_requests(session.advance_frame(), session)
                except PredictionThreshold:
                    pass
            drive_spectator(spec_session, spec_runner)

        # Spectator advanced a meaningful number of confirmed frames.
        assert spec_runner.frame >= 40
        # Spectator never rolled back (`run_spectator` never emits loads).
        assert spec_runner.rollbacks_total == 0

        # Its world at frame F must equal the true confirmed trajectory at F:
        # both players' inputs are a deterministic script, so replay them
        # through a fresh serial run and compare translations bitwise.
        ref = RollbackRunner(
            box_game.make_schedule(),
            box_game.make_world(2).commit(),
            max_prediction=8,
            num_players=2,
            input_spec=box_game.INPUT_SPEC,
        )
        from bevy_ggrs_tpu.session.requests import AdvanceFrame

        for f in range(spec_runner.frame):
            bits = np.stack([scripted_input(h, f) for h in range(2)])
            ref.handle_requests(
                [AdvanceFrame(bits=bits, status=np.zeros(2, np.int32))]
            )
        a = spec_runner.world()["components"]["translation"]
        b = ref.world()["components"]["translation"]
        np.testing.assert_array_equal(a, b)

    def test_spectator_waits_without_host_data(self):
        net = LoopbackNetwork()
        # Host exists but never sends inputs (no local advance).
        peers = make_pair(net, spectators=[("spec", 0)])
        spec_session, spec_runner = make_spectator(net, ("peer", 0))
        # Let sync complete (host polls, spectator polls).
        for _ in range(20):
            net.advance(FPS_DT)
            for session, _ in peers:
                session.poll_remote_clients()
            spec_session.poll_remote_clients()
        assert spec_session.current_state() == SessionState.RUNNING
        try:
            spec_session.advance_frame()
            advanced = True
        except PredictionThreshold:
            advanced = False
        assert not advanced
        assert spec_runner.frame == 0

    def test_spectator_acks_bound_host_pending(self):
        """Regression: spectators must ack received inputs, else the host's
        per-spectator unacked span grows O(frames) and eventually overflows
        the wire format's uint16 span length."""
        net = LoopbackNetwork()
        peers = make_pair(net, spectators=[("spec", 0)])
        spec_session, spec_runner = make_spectator(net, ("peer", 0))
        for _ in range(150):
            net.advance(FPS_DT)
            for session, runner in peers:
                session.poll_remote_clients()
                if session.current_state() != SessionState.RUNNING:
                    continue
                for h in session.local_player_handles():
                    session.add_local_input(h, scripted_input(h, session.current_frame))
                try:
                    runner.handle_requests(session.advance_frame(), session)
                except PredictionThreshold:
                    pass
            drive_spectator(spec_session, spec_runner)
        host_session, _ = peers[0]
        pending = host_session._endpoints[("spec", 0)]._pending_output
        worst = max((len(d) for d in pending.values()), default=0)
        assert worst < 20, f"host pending to spectator grew to {worst} frames"

    def test_spectator_contributes_no_input(self):
        net = LoopbackNetwork()
        spec_session, _ = make_spectator(net, ("peer", 0))
        assert spec_session.local_player_handles() == []

    def test_catchup_burst_is_hard_capped_per_call(self):
        """A spectator hundreds of frames behind (shed/partition resume)
        must converge over several polls, never one unbounded dispatch
        burst — ``CATCHUP_BURST_CAP`` binds even a huge
        ``max_frames_behind``."""
        from bevy_ggrs_tpu.session.endpoint import PeerState
        from bevy_ggrs_tpu.session.spectator import (
            CATCHUP_BURST_CAP,
            SpectatorSession,
        )

        net = LoopbackNetwork()
        session = SpectatorSession(
            2,
            box_game.INPUT_SPEC,
            net.socket(("spec", 9)),
            ("peer", 0),
            max_frames_behind=10_000,
            clock=lambda: net.now,
        )
        session._endpoint.state = PeerState.RUNNING
        for h in range(2):
            for f in range(500):
                session._queues[h].add_input(f, scripted_input(h, f))

        requests = session.advance_frame()
        assert len(requests) == CATCHUP_BURST_CAP
        assert session.current_frame == CATCHUP_BURST_CAP
        # Repeated calls drain the backlog in bounded slices.
        total = len(requests)
        while session.current_frame < 499:
            batch = session.advance_frame()
            assert 1 <= len(batch) <= CATCHUP_BURST_CAP
            total += len(batch)
        assert total == session.current_frame


def test_the_poll_split_is_the_callers_and_changes_nothing(monkeypatch):
    """``parts`` as ``P2PSession``'s (PR 35): a caller that passes its
    two-slot list gets the poll's receive and send seconds added in; with
    ``parts=None`` the clock is not read; the spectator ends on the same
    frame either way."""
    import time

    def run(parts):
        net = LoopbackNetwork()
        peers = make_pair(net, spectators=[("spec", 0)])
        spec_session, spec_runner = make_spectator(net, ("peer", 0))
        reads = [0]
        real = time.perf_counter

        def counting():
            reads[0] += 1
            return real()

        for _ in range(90):
            drive(net, peers, scripted_input, 1)
            with monkeypatch.context() as patch:
                patch.setattr(time, "perf_counter", counting)
                spec_session.poll_remote_clients(parts=parts)
            if spec_session.current_state() == SessionState.RUNNING:
                try:
                    spec_runner.handle_requests(
                        spec_session.advance_frame(), spec_session)
                except PredictionThreshold:
                    pass
        return spec_session.current_frame, reads[0]

    frame_off, reads_off = run(None)
    parts = [0.0, 0.0]
    frame_on, reads_on = run(parts)
    assert frame_on == frame_off > 0
    assert reads_off == 0 and reads_on == 3 * 90
    assert parts[0] > 0.0 and parts[1] > 0.0

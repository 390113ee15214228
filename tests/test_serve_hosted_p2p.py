"""Hosted P2P matches on the normal path: ``MatchServer.add_match(session,
local_inputs)`` with ``session = SessionBuilder(...).start_p2p_session(...)``
at the builder's defaults, player 0 local on the server and player 1 a
remote peer behind a lossy network that also loses everything in bursts.

Eight matches in two stagger groups (the shape of the benchmark's toy size)
against per-match serial ``RollbackRunner`` peers on one virtual clock: the
served states are bitwise the peers', no desync, ballots compared; a frame
withheld by back-pressure is counted (``frames_withheld_total``) once per
``PredictionThreshold`` a session raised; ``burst_steps_total`` equals a
count taken from the request lists and ``burst_step_slots_total`` lies
where the deepest list of each dispatch puts it; the
native batch plane and the per-slot Python path (``GGRS_NO_NATIVE=1``)
agree on all of it.
"""

import functools

import numpy as np
import pytest

from bevy_ggrs_tpu.chaos import ChaosPlan, ChaosSocket, LossBurst
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.native import core as ncore
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.serve import MatchServer
from bevy_ggrs_tpu.session import (
    PlayerType,
    PredictionThreshold,
    SessionBuilder,
    SessionState,
)
from bevy_ggrs_tpu.session.requests import AdvanceFrame
from bevy_ggrs_tpu.state import ring_load
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_p2p import FPS_DT, scripted_input

MATCHES, GROUPS = 8, 2
MAX_PRED = BRANCHES = SPEC_FRAMES = 8
FRAMES = 150
PLANES = [
    pytest.param("native", marks=pytest.mark.skipif(
        not ncore.available(), reason="native session core did not build")),
    "python",
]


def _session(net, me, k, metrics):
    """One end of match ``k`` at the SessionBuilder defaults: window 8,
    input delay 0, disconnect timeout 2.0 s, desync detection auto."""
    names = ("srv", "ext")
    builder = SessionBuilder(box_game.INPUT_SPEC).with_num_players(2)
    for h in range(2):
        builder.add_player(
            PlayerType.local() if h == me
            else PlayerType.remote((names[h], k)), h,
        )
    return builder.start_p2p_session(
        net.socket((names[me], k)), clock=lambda: net.now, metrics=metrics
    )


def _bursts(k):
    """Match ``k``'s far end sends nothing for 12 frames (longer than the
    window: the server must withhold) and, later, for 5."""
    a, b = 50 + 3 * k, 100 + 2 * k
    return ChaosPlan(k, (
        LossBurst((a - 0.5) * FPS_DT, (a + 11.5) * FPS_DT, 1.0),
        LossBurst((b - 0.5) * FPS_DT, (b + 4.5) * FPS_DT, 1.0),
    ))


def _counted(session, log, key):
    """Wrap ``advance_frame``: count every PredictionThreshold raised, and
    record every request list returned as (``key()``, its AdvanceFrames)."""
    inner = session.advance_frame

    def advance_frame():
        try:
            requests = inner()
        except PredictionThreshold:
            log["raised"] += 1
            raise
        log["lists"].append(
            (key(), sum(isinstance(r, AdvanceFrame) for r in requests))
        )
        return requests

    session.advance_frame = advance_frame


class _Peer:
    """A remote client with a serial runner (the supervisor drive loop
    without a supervisor: poll, input, advance, execute)."""

    def __init__(self, session):
        self.session = session
        self.runner = RollbackRunner(
            box_game.make_schedule(), box_game.make_world(2).commit(),
            max_prediction=MAX_PRED, num_players=2,
            input_spec=box_game.INPUT_SPEC,
        )
        self.desyncs = 0

    def tick(self, advance=True):
        from bevy_ggrs_tpu.session.common import EventKind

        s = self.session
        s.poll_remote_clients()
        self.desyncs += sum(
            ev.kind == EventKind.DESYNC_DETECTED for ev in s.events()
        )
        if not advance or s.current_state() != SessionState.RUNNING:
            return
        for h in s.local_player_handles():
            s.add_local_input(h, scripted_input(h, s.current_frame))
        try:
            requests = s.advance_frame()
        except PredictionThreshold:
            return
        self.runner.handle_requests(requests, s)


def _server(plane, metrics):
    """The eight-slot, two-group server of these tests, warmed, on the
    native batch plane or the per-slot Python path."""
    server = MatchServer(
        box_game.make_schedule(), box_game.make_world(2).commit(),
        MAX_PRED, 2, box_game.INPUT_SPEC,
        capacity=MATCHES, stagger_groups=GROUPS,
        num_branches=BRANCHES, spec_frames=SPEC_FRAMES, metrics=metrics,
    )
    if plane == "python":
        for core in server.groups:
            core._plane = None  # the GGRS_NO_NATIVE=1 route of _dispatch
    server.warmup()
    return server


@functools.lru_cache(maxsize=None)
def served(plane):
    """Drive the eight matches once per plane; everything the tests
    read."""
    net = LoopbackNetwork(
        latency=2 * FPS_DT, jitter=1 * FPS_DT, loss=0.03, seed=11
    )
    metrics, host_metrics, peer_metrics = Metrics(), Metrics(), Metrics()
    server = _server(plane, metrics)
    base = {
        "steps": sum(g.burst_steps_total for g in server.groups),
        "slots": sum(g.burst_step_slots_total for g in server.groups),
    }
    logs = [{"raised": 0, "lists": []} for _ in range(MATCHES)]
    hosts, peers, handles = [], [], []
    for k in range(MATCHES):
        host = _session(net, 0, k, host_metrics)
        far = _session(net, 1, k, peer_metrics)
        far.socket = ChaosSocket(
            far.socket, _bursts(k), clock=lambda: net.now, addr=("ext", k)
        )
        handle = server.add_match(
            host, lambda frame, h: scripted_input(h, frame)
        )
        _counted(host, logs[k],
                 lambda g=handle.group: (server.frames_served, g))
        hosts.append(host)
        peers.append(_Peer(far))
        handles.append(handle)
    for _ in range(FRAMES):
        net.advance(FPS_DT)
        for p in peers:
            p.tick()
        server.run_frame()
    # Let what is in flight land (nobody advances), then one more step of
    # both ends: every snapshot up to confirmed + 1 rests on confirmed
    # inputs only.
    for _ in range(60):
        net.advance(FPS_DT)
        for core in server.groups:
            core.flush_reports()
        for host, p in zip(hosts, peers):
            host.poll_remote_clients()
            p.tick(advance=False)
    net.advance(FPS_DT)
    server.run_frame()
    for p in peers:
        p.tick()
    pairs = []
    for host, p, h in zip(hosts, peers, handles):
        upto = min(host.confirmed_frame() + 1, host.current_frame - 1,
                   p.session.confirmed_frame() + 1,
                   p.session.current_frame - 1)
        core = server.groups[h.group]
        pairs.append((
            upto,
            ring_load(core.slot_ring(h.slot), upto),
            ring_load(p.runner.ring, upto),
            int(np.asarray(core.rings.frames)[h.slot][
                upto % core.ring_depth]),
        ))
    return {
        "server": server, "metrics": metrics, "logs": logs, "pairs": pairs,
        "handles": handles,
        "raised": sum(log["raised"] for log in logs),
        "lists": [entry for log in logs for entry in log["lists"]],
        "host_metrics": host_metrics, "peer_metrics": peer_metrics,
        "peer_desyncs": sum(p.desyncs for p in peers),
        "frames": [server.groups[h.group].slots[h.slot].frame
                   for h in handles],
        "steps": sum(g.burst_steps_total for g in server.groups)
        - base["steps"],
        "slots": sum(g.burst_step_slots_total for g in server.groups)
        - base["slots"],
        "rollbacks": sum(g.rollbacks_total for g in server.groups),
        "checksums": [np.asarray(g.rings.checksums) for g in server.groups],
    }


def _tree_equal(a, b):
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


@pytest.mark.parametrize("match", range(MATCHES))
@pytest.mark.parametrize("plane", PLANES)
def test_hosted_match_is_bitwise_its_serial_peer(plane, match):
    upto, ours, theirs, ring_frame = served(plane)["pairs"][match]
    assert upto > FRAMES // 2
    assert ring_frame == upto          # the frame is still in the ring
    assert _tree_equal(ours, theirs)


@pytest.mark.parametrize("plane", PLANES)
def test_no_desync_no_fault_and_ballots_compared(plane):
    r = served(plane)
    server = r["server"]
    assert r["host_metrics"].counters.get("desyncs_flagged", 0) == 0
    assert r["peer_metrics"].counters.get("desyncs_flagged", 0) == 0
    assert r["peer_desyncs"] == 0
    assert r["host_metrics"].counters["checksum_ballots"] > MATCHES
    assert r["peer_metrics"].counters["checksum_ballots"] > MATCHES
    assert server.faults_total == 0 and server.evictions_total == 0
    assert server.slots_quarantined == server.slots_recovering == 0
    assert min(r["frames"]) > FRAMES // 2
    assert r["rollbacks"] > MATCHES    # late remote input did roll back


@pytest.mark.parametrize("plane", PLANES)
def test_withheld_frames_are_the_prediction_thresholds_raised(plane):
    r = served(plane)
    server = r["server"]
    # A burst of 12 frames is longer than the window: every match was
    # withheld some frames, and each is counted exactly once.
    assert all(log["raised"] > 0 for log in r["logs"])
    assert server.frames_withheld_total == r["raised"]
    assert r["metrics"].counters["frames_withheld"] == r["raised"]
    # Every request list advanced its match one frame; what a served frame
    # neither advanced nor withheld was still synchronising.
    assert r["frames"] == [len(log["lists"]) for log in r["logs"]]
    assert sum(r["frames"]) + r["raised"] <= server.frames_served * MATCHES
    # The withheld frame reaches the SLO sample too: one a tick from
    # RUNNING on, as a tick and not as a fault.
    for log, handle in zip(r["logs"], r["handles"]):
        window = server.slo._slots[server._flat_slot(handle)].bad
        ticks = len(log["lists"]) + log["raised"]
        assert len(window["deadline"]) == min(
            window["deadline"].maxlen, ticks)
        assert not any(window["quarantine"])


@pytest.mark.parametrize("plane", PLANES)
def test_burst_step_counters_equal_the_request_lists(plane):
    r = served(plane)
    core = r["server"].groups[0]
    assert core.burst_frames == MAX_PRED + 2
    asked = sum(n for _, n in r["lists"])
    assert r["steps"] == asked
    # A dispatch runs its deepest lane's burst for every lane of the group:
    # no more than the deepest list asked for, and less only by the frames
    # that lane's rollback recovered from its rollout (they take no step).
    deepest = {}
    for key, n in r["lists"]:
        deepest[key] = max(deepest.get(key, 0), n)
    recovered = sum(
        g.rollback_frames_recovered_total for g in r["server"].groups)
    most = core.num_slots * sum(deepest.values())
    assert recovered > 0
    assert most - core.num_slots * recovered <= r["slots"] <= most
    assert asked - recovered <= r["slots"]
    # A lane asks one step, or up to window + 1 when it rolls back.
    assert max(deepest.values()) > 4
    assert 0.3 < r["steps"] / r["slots"] < 1.0


@pytest.mark.parametrize("plane", PLANES)
def test_poll_series_has_one_sample_a_group_tick(plane):
    series = served(plane)["metrics"].series
    assert len(series["serve_poll_ms"]) == len(series["serve_sessions_ms"])
    assert len(series["serve_poll_ms"]) > FRAMES
    assert all(0 < p <= s for p, s in zip(series["serve_poll_ms"],
                                          series["serve_sessions_ms"]))


@pytest.mark.parametrize("plane", PLANES)
def test_session_sums_add_up_to_the_span_a_group_tick(plane):
    """Hosted P2P: the five sums of span ``serve_sessions`` (one sample a
    group tick each) and what they leave (``serve_sessions_other_ms``) are
    the span; the polls' two sides, which the SERVER asks its sessions for
    (``poll_remote_clients(parts=...)``), lie inside the polls' sum."""
    series = served(plane)["metrics"].series
    spans = series["serve_sessions_ms"]
    sums = ("serve_supervisor_ms", "serve_poll_ms", "serve_local_inputs_ms",
            "serve_advance_ms", "serve_slo_ms")
    for key in sums + ("serve_sessions_other_ms", "serve_poll_recv_ms",
                       "serve_poll_send_ms", "serve_tick_other_ms"):
        assert len(series[key]) == len(spans), key
    # The core's own spans: one a tick() it was called for (none while
    # every match of the group was still synchronizing).
    ticks = len(series["serve_rounds"])
    assert len(series["serve_segment_ms"]) == ticks < len(spans)
    # (+ 1: warm-up's one dispatch, outside any tick.)
    assert len(series["serve_post_ms"]) == ticks + 1
    assert len(series["serve_arg_assembly_ms"]) == ticks + 1
    for i, span_ms in enumerate(spans):
        other = series["serve_sessions_other_ms"][i]
        assert other >= 0.0
        assert sum(series[k][i] for k in sums) + other == pytest.approx(
            span_ms, abs=1e-6)
        recv, send = (series["serve_poll_recv_ms"][i],
                      series["serve_poll_send_ms"][i])
        assert 0.0 < recv and 0.0 < send
        assert recv + send <= series["serve_poll_ms"][i]
    # Every hosted match has a supervisor, local inputs and an advance.
    for key in sums:
        assert min(series[key][-FRAMES:]) > 0.0, key
    assert all(v >= 0.0 for v in series["serve_tick_other_ms"])
    # The hosted sessions hold a Metrics of their own (for their counters):
    # they time nothing by themselves, whoever listens.
    r = served(plane)
    for m in (r["host_metrics"], r["peer_metrics"]):
        assert not any(k.startswith(("poll", "serve_")) for k in m.series)


@pytest.mark.parametrize("plane", PLANES)
def test_direct_share_is_the_sessions_own_counts(plane):
    """``serve_poll_direct_share``: of the datagrams a group's polls
    received, the share parsed in place; a sample a group tick in which
    one arrived, none while nothing did (the first tick of all)."""
    r = served(plane)
    series = r["metrics"].series
    share = series["serve_poll_direct_share"]
    assert 0 < len(share) <= len(series["serve_poll_ms"])
    assert all(0.0 <= v <= 100.0 for v in share)
    # Handshake legs are decoded (0 %), then play: input messages, with a
    # quality report or a checksum now and then.
    assert share[0] == 0.0 and max(share) == 100.0
    assert sorted(share)[len(share) // 2] > 75.0
    counters = r["host_metrics"].counters
    assert 0 < counters["datagrams_in_direct"] < counters["datagrams_in"]
    assert counters["datagrams_in_direct"] > 0.5 * counters["datagrams_in"]


def test_native_plane_and_python_path_agree():
    nat, py = served("native"), served("python")
    assert nat["server"].groups[0]._plane is not None
    assert py["server"].groups[0]._plane is None
    for key in ("frames", "steps", "slots", "rollbacks"):
        assert nat[key] == py[key], key
    assert nat["logs"] == py["logs"]
    assert (nat["server"].frames_withheld_total
            == py["server"].frames_withheld_total)
    for a, b in zip(nat["checksums"], py["checksums"]):
        assert np.array_equal(a, b)
    for (ua, sa, _, _), (ub, sb, _, _) in zip(nat["pairs"], py["pairs"]):
        assert ua == ub and _tree_equal(sa, sb)


@pytest.mark.parametrize("plane", PLANES)
def test_repack_moves_a_hosted_match_in_mid_match(plane):
    """Off-peak the server re-packs its survivors (``_repack``): a hosted
    P2P match moved between two frames keeps its ``_Match`` record and its
    ``SessionSupervisor`` (the same object, its runner facade on the new
    slot), raises no desync against its far end over the frames after, and
    the far end sees every frame confirmed in turn."""
    from bevy_ggrs_tpu.serve.faults import _SlotRunnerFacade

    net = LoopbackNetwork(latency=1 * FPS_DT, jitter=0.0, loss=0.0, seed=5)
    metrics, host_metrics, peer_metrics = Metrics(), Metrics(), Metrics()
    server = _server(plane, metrics)
    hosts, peers, handles = [], [], []
    for k in range(MATCHES):
        hosts.append(_session(net, 0, k, host_metrics))
        peers.append(_Peer(_session(net, 1, k, peer_metrics)))
        handles.append(server.add_match(
            hosts[k], lambda frame, h: scripted_input(h, frame)))
    live = [1, 6]                    # one survivor a group

    def step(ks):
        net.advance(FPS_DT)
        for k in ks:
            peers[k].tick()
        server.run_frame()

    for _ in range(40):
        step(range(MATCHES))
    assert [tuple(handles[k]) for k in live] == [(0, 1), (1, 2)]
    moved = handles[6]
    record = server._matches[moved]
    supervisor = record.supervisor
    assert supervisor is not None
    ballots = host_metrics.counters["checksum_ballots"]
    for k in range(MATCHES):
        if k not in live:
            server.retire_match(handles[k])
    # The evening is over: the next frame's end moves match 6 to group 0.
    confirmed = []
    for i in range(90):
        step(live)
        confirmed.append(peers[6].session.confirmed_frame())
        if i == 0:
            assert tuple(moved) == (0, 0)
            assert metrics.counters["matches_repacked"] == 1
    assert metrics.counters["matches_repacked"] == 1
    assert server.groups[1].active_count == 0
    assert metrics.series["serve_hot_groups"][-89:] == [1.0] * 89
    # The same record, session and supervisor, re-pointed.
    assert server._matches[moved] is record
    assert record.session is hosts[6] and record.supervisor is supervisor
    facade = supervisor.runner
    assert isinstance(facade, _SlotRunnerFacade)
    assert facade._core is server.groups[0] and facade._slot == 0
    assert facade.frame == server.groups[0].slots[0].frame
    # No desync, ballots compared after the move, nothing fenced.
    assert host_metrics.counters.get("desyncs_flagged", 0) == 0
    assert peer_metrics.counters.get("desyncs_flagged", 0) == 0
    assert sum(p.desyncs for p in peers) == 0
    assert host_metrics.counters["checksum_ballots"] > ballots + 4
    assert server.faults_total == 0 and server.frames_withheld_total == 0
    # The far end saw no gap: every frame confirmed, one a tick.
    assert np.all(np.diff(confirmed) == 1)
    # ... and holds the served match's state, bit for bit.
    host, p = hosts[6], peers[6]
    upto = min(host.confirmed_frame() + 1, host.current_frame - 1,
               p.session.confirmed_frame() + 1, p.session.current_frame - 1)
    assert upto > 100
    core = server.groups[moved.group]
    assert _tree_equal(ring_load(core.slot_ring(moved.slot), upto),
                       ring_load(p.runner.ring, upto))

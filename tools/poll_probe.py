#!/usr/bin/env python3
"""What one ``poll_remote_clients()`` of a hosted match costs the host
(ROADMAP S4 (b), step 0 of a PR on the session layer).

    python3 tools/poll_probe.py [--matches 64] [--endpoints 1] [--ticks 400]
                                [--profile]

``--matches`` hosted box_game matches, sessions only (no runner, no device):
seat 0 of each is a ``P2PSession`` on "the server", the other
``--endpoints`` seats are far ends, all on one ``LoopbackNetwork`` at mix
``wan``'s latency, jitter and loss (2 frames, 1 frame, 3 %), the virtual clock
moving 1/60 s a tick. ``--endpoints 1`` is a group of ``server256.wan`` (64
duels, window 8), ``--endpoints 7`` the sessions of ``hosted8.wan`` (window
12, input delay 2; 16 lobbies make its group: ``--matches 16``).

Times, host clock, the SERVER's sessions only, after the handshakes and 60
ticks of play: a poll (with its two sides, as ``parts`` hands them out) and
an ``advance_frame()``, the median over the ticks of the mean a session, in
microseconds; the datagrams a poll took, the share that were ``InputMsg``s
and, where the session counts it, the share parsed in place. ``--profile``
prints ``cProfile``'s top of the server's polls instead of timing them. The
far ends' time is not counted. ``GGRS_NO_NATIVE=1`` probes the Python plane.
On the CPU of this sandbox the numbers are about 1.65 x shorter than on the
chip machine's host (PR 56): read the shares, not the microseconds.
"""

from __future__ import annotations

import argparse
import cProfile
import os
import pstats
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np

from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.session import (
    PlayerType,
    PredictionThreshold,
    SessionBuilder,
    SessionState,
)
from bevy_ggrs_tpu.session import protocol as proto
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from bevy_ggrs_tpu.utils.metrics import Metrics

FPS_DT = 1.0 / 60.0
KEYS = (0, 1, 2, 4, 5, 6, 8, 9, 10)


class CountingNetwork(LoopbackNetwork):
    """Counts what is sent to a server seat, by message type."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.to_server = 0
        self.inputs_to_server = 0

    def _send(self, src, dst, msg):
        if dst[1] == 0:
            self.to_server += 1
            self.inputs_to_server += msg[2] == proto.T_INPUT
        super()._send(src, dst, msg)


def build(net, match, seat, players, window, delay, metrics):
    b = (
        SessionBuilder(box_game.INPUT_SPEC)
        .with_num_players(players)
        .with_max_prediction_window(window)
        .with_input_delay(delay)
        .with_seed(match * 16 + seat)
    )
    for h in range(players):
        b.add_player(
            PlayerType.local() if h == seat
            else PlayerType.remote((match, h)), h)
    return b.start_p2p_session(
        net.socket((match, seat)), clock=lambda: net.now, metrics=metrics)


def step(session, seat, keys):
    if session.current_state() != SessionState.RUNNING:
        return
    session.add_local_input(seat, keys)
    try:
        session.advance_frame()
    except PredictionThreshold:
        pass


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--matches", type=int, default=64)
    ap.add_argument("--endpoints", type=int, default=1)
    ap.add_argument("--ticks", type=int, default=400)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--profile", action="store_true")
    args = ap.parse_args()
    players = args.endpoints + 1
    window, delay = (8, 0) if players == 2 else (12, 2)
    net = CountingNetwork(
        latency=2 * FPS_DT, jitter=FPS_DT, loss=0.03, seed=args.seed)
    counts = Metrics()
    servers = [
        build(net, m, 0, players, window, delay, counts)
        for m in range(args.matches)
    ]
    far = [
        [build(net, m, s, players, window, delay, None)
         for s in range(1, players)]
        for m in range(args.matches)
    ]
    rng = np.random.RandomState(args.seed)
    held = rng.choice(KEYS, size=(args.matches, players)).astype(np.uint8)

    def tick(timed=None, profile=None):
        net.advance(FPS_DT)
        change = rng.rand(args.matches, players) < 0.1
        held[change] = rng.choice(KEYS, size=int(change.sum()))
        parts = [0.0, 0.0]
        t_0 = time.perf_counter()
        if profile is not None:
            profile.enable()
        for s in servers:
            s.poll_remote_clients(parts=parts)
        if profile is not None:
            profile.disable()
        t_1 = time.perf_counter()
        for m, s in enumerate(servers):
            step(s, 0, held[m, 0])
        t_2 = time.perf_counter()
        if timed is not None:
            timed.append((t_1 - t_0, parts[0], parts[1], t_2 - t_1))
        for m, ends in enumerate(far):
            for seat, s in enumerate(ends, 1):
                s.poll_remote_clients()
                step(s, seat, held[m, seat])

    warm = 0
    while not all(
        s.current_state() == SessionState.RUNNING
        for s in servers + [s for ends in far for s in ends]
    ):
        tick()
        warm += 1
        if warm > 3000:
            raise SystemExit("the lobbies did not form in 3000 ticks")
    for _ in range(60):
        tick()
    in_0 = (net.to_server, net.inputs_to_server)
    got_0 = dict(counts.counters)
    timed, profile = [], cProfile.Profile() if args.profile else None
    for _ in range(args.ticks):
        tick(timed, profile)
    n = args.matches
    polls = args.ticks * n
    sent, inputs = net.to_server - in_0[0], net.inputs_to_server - in_0[1]
    got = {
        k: counts.counters.get(k, 0) - got_0.get(k, 0)
        for k in ("datagrams_in", "datagrams_in_direct", "datagrams_out")
    }
    print(
        f"{n} matches x {args.endpoints} endpoint(s), window {window}, "
        f"delay {delay}, {type(servers[0]._qset).__name__}; handshakes "
        f"{warm} ticks; {args.ticks} ticks timed, frame "
        f"{servers[0].current_frame}"
    )
    print(
        f"a poll: {got['datagrams_in'] / polls:.2f} datagrams in "
        f"({100.0 * inputs / max(sent, 1):.1f} % of those sent to a server "
        f"are InputMsg; {100.0 * got['datagrams_in_direct'] / max(got['datagrams_in'], 1):.1f} % "
        f"parsed in place), {got['datagrams_out'] / polls:.2f} out"
    )
    if profile is not None:
        pstats.Stats(profile).sort_stats("tottime").print_stats(28)
        return
    for name, i in (("poll", 0), ("  receive side", 1), ("  send side", 2),
                    ("advance_frame", 3)):
        us = [row[i] / n * 1e6 for row in timed]
        print(
            f"{name:<16} {statistics.median(us):8.2f} us a session "
            f"(p90 {statistics.quantiles(us, n=10)[-1]:.2f})"
        )


if __name__ == "__main__":
    main()

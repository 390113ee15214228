"""Loop kind ``match_server_mesh``: ``match_server_p2p`` for lobbies, a
hosted match with more than one remote player and an input delay.

One ``MatchServer`` whose every match is a hosted P2P session of ``P``
players in a full mesh: player 0 local on the server (the operator's seat),
players ``1 .. P-1`` remote clients, so a hosted session polls ``P - 1``
endpoints and a lobby has ``P (P - 1) / 2`` links. The window loop, the
withheld count, the counters and every ``guarantee.*`` / ``reference.*``
row are ``match_server_p2p``'s, inherited; what differs:

- a match has ``P - 1`` far ends (``match_server_p2p.FarEnd``, each a
  ``P2PSession`` of ``P - 1`` endpoints behind a ``ChaosSocket`` with a
  burst plan of its own), kept flat in ``self.far`` for the inherited loop
  and by match in ``self.far_of``. Peers ``2 .. P-1`` are session-only. In
  the sampled matches peer 1 resimulates every rollback and reports
  checksums, so ballots are compared: through the packed fused tick of
  ``peer1_speculation_branches`` branches (``p2p_mesh``'s peer 1; a third
  of the serial executor's host cost);
- with an input delay ``d`` a session commits what a player holds at frame
  ``f`` to frame ``f + d`` (``p2p_mesh``'s docstring): the sessions are fed
  the generator's table (``self.held``), and ``self.keys``, which the
  inherited ``check()`` replays through the serial oracle and the plain
  reference, is that table **shifted** (``p2p_mesh.shifted_table``);
  ``check()`` adds ``guarantee.inputs_differ_from_shifted_table``, held to
  the confirmed inputs every hosted session still has.
"""

from __future__ import annotations

from typing import List

import numpy as np

from benchmark.bursts import LossBursts
from benchmark.drivers.common import Comparison, Context
from benchmark.drivers.match_server_p2p import Driver as MatchServerP2PDriver
from benchmark.drivers.p2p_mesh import _CountingFarEnd, shifted_table
from benchmark.inputs import HeldKeys, network_seed
from bevy_ggrs_tpu.session.common import SessionState


class _ShiftedKeys:
    """A ``HeldKeys`` table as the simulation used it under an input
    delay: ``table(frames)`` alone, what ``check()`` asks of ``keys``."""

    def __init__(self, held: HeldKeys, delay: int):
        self._held, self._delay = held, delay

    def table(self, frames: int) -> np.ndarray:
        return shifted_table(self._held.table(frames), self._delay)


class _FusedPeer:
    """A ``SpeculativeRollbackRunner`` under the names a far end uses of
    its runner: a tick is one fused dispatch (``GGRSStage._step_p2p``)."""

    def __init__(self, runner):
        self.runner = runner

    @property
    def state(self):
        return self.runner.state

    def handle_requests(self, requests, session) -> None:
        self.runner.tick(requests, session.confirmed_frame(), session)

    def flush_reports(self, session) -> None:
        self.runner.flush_reports(session)


class _ResimulatingFarEnd(_CountingFarEnd):
    """Peer 1 of a sampled match: its deferred checksum reports land
    before its session polls, as ``GGRSStage`` orders them."""

    def poll(self) -> None:
        self.runner.flush_reports(self.session)
        super().poll()


class Driver(MatchServerP2PDriver):
    def __init__(self, ctx: Context):
        super().__init__(ctx)
        self.delay = int(ctx.config["settings"]["input_delay"])
        if self.players < 3:
            raise ValueError("a hosted lobby has the server's seat, a "
                             "resimulating peer and a session-only far end")

    # -- set-up ---------------------------------------------------------

    def _session(self, me: int, k: int, metrics=None):
        """Peer ``me`` of lobby ``k``: handle ``me`` local at
        ("peer", k, me), every other handle remote."""
        from bevy_ggrs_tpu.session import PlayerType, SessionBuilder

        s = self.ctx.config["settings"]
        builder = (
            SessionBuilder(self.ctx.title.input_spec())
            .with_num_players(self.players)
            .with_max_prediction_window(int(s["max_prediction"]))
            .with_input_delay(self.delay)
            .with_fps(self.fps)
            .with_disconnect_timeout(float(s["disconnect_timeout_s"]))
            .with_desync_detection(s["desync_detection"])
        )
        for h in range(self.players):
            builder.add_player(
                PlayerType.local() if h == me
                else PlayerType.remote(("peer", k, h)), h)
        return builder.start_p2p_session(
            self.net.socket(("peer", k, me)), clock=lambda: self.net.now,
            metrics=metrics)

    def _feed(self, match: int):
        held = self.held
        return lambda frame, handle: held.bits(match, frame, handle)

    def _fused_peer(self) -> _FusedPeer:
        from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner

        s = self.ctx.config["settings"]
        runner = SpeculativeRollbackRunner(
            self.schedule, self.initial, int(s["max_prediction"]),
            self.players, self.ctx.title.input_spec(),
            num_branches=int(s["peer1_speculation_branches"]))
        runner.warmup()
        return _FusedPeer(runner)

    @staticmethod
    def _refuse_a_select_chain(branches: int) -> None:
        """A program older than PR 39 reads a slot's matched branch by a
        chain of one select a branch (``state.py`` ``_row_read_at``): at
        1,024 branches 20,000 operations, 200 MB of code and six minutes of
        compiling a process. Refused at once, by name, and not run."""
        from bevy_ggrs_tpu import state

        if branches > 64 and not hasattr(state, "SELECT_ROWS"):
            raise RuntimeError(
                f"this program's [S]-vmapped tick reads the matched one of "
                f"{branches} branches by a chain of {branches} selects a "
                "leaf (no state.SELECT_ROWS): not run")

    def setup(self, mark=lambda name: None) -> None:
        from bevy_ggrs_tpu.chaos import ChaosSocket
        from bevy_ggrs_tpu.serve.server import MatchServer
        from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
        from bevy_ggrs_tpu.utils.metrics import Metrics

        ctx, s = self.ctx, self.ctx.config["settings"]
        occ, netp = ctx.traffic["occupancy"], ctx.traffic["network"]
        live = int(occ["live"])
        if int(occ["admit"]) != live or live > self.capacity:
            raise ValueError("occupancy does not fit the configuration")
        self._refuse_a_select_chain(int(s["speculation_branches"]))
        if ctx.trace:
            self.program_metrics = Metrics()
        self.schedule = ctx.title.make_schedule(ctx.control)
        self.initial = ctx.title.make_world(self.players)
        self.server = MatchServer(
            self.schedule, self.initial, int(s["max_prediction"]),
            self.players, ctx.title.input_spec(),
            capacity=self.capacity, stagger_groups=int(s["stagger_groups"]),
            num_branches=int(s["speculation_branches"]),
            spec_frames=int(s["speculation_frames"]),
            metrics=self.program_metrics,
        )
        mark("server_built")
        self.server.warmup()
        mark("server_warm")
        if self.program_metrics is not None:
            carried = self.program_metrics.series.get("serve_carry_bytes")
            if carried:
                self.scalars["serve_carry_bytes"] = float(carried[-1])
        self.held = HeldKeys(ctx.seed, live, self.players,
                             ctx.traffic["inputs"])
        self.held.table(int(ctx.traffic["inputs_horizon_frames"]))
        self.keys = _ShiftedKeys(self.held, self.delay)
        self.net = LoopbackNetwork(
            latency=float(netp["latency_frames"]) * self.dt,
            jitter=float(netp["jitter_frames"]) * self.dt,
            loss=float(netp["loss"]), seed=network_seed(ctx.seed))
        self.bursts = LossBursts(ctx.seed, ctx.traffic["bursts"])
        rng = np.random.Generator(np.random.PCG64([ctx.seed, 0xC0FFEE]))
        self.sample = sorted(int(k) for k in rng.choice(
            live, size=min(int(ctx.traffic["sample_slots"]), live),
            replace=False))
        # As ``match_server_p2p``: exact counters of the hosted sessions
        # and of the resimulating far ends (the only ones that compare).
        self.host_metrics, self.far_metrics = Metrics(), Metrics()
        self.hosts, self.far, self.far_of = [], [], []
        self.live = {}
        remote = self.players - 1
        for k in range(live):
            feed = self._feed(k)
            host = self._session(0, k, self.host_metrics)
            ends = []
            for h in range(1, self.players):
                resimulates = h == 1 and k in self.sample
                far = self._session(
                    h, k, self.far_metrics if resimulates else None)
                far.socket = ChaosSocket(
                    far.socket, self.bursts.plan(k * remote + h - 1, self.dt),
                    clock=self._burst_clock, addr=("peer", k, h))
                ends.append(
                    _ResimulatingFarEnd(far, feed, self._fused_peer())
                    if resimulates else _CountingFarEnd(far, feed))
            self.hosts.append(host)
            self.far_of.append(ends)
            self.far += ends
            self.live[k] = self.server.add_match(host, feed)
        mark("matches_admitted")
        self.oracle = self._oracle()
        self.oracle.warmup()
        mark("oracle_warm")
        limit = int(ctx.traffic["sync_frames_limit"])
        running = lambda: all(  # noqa: E731
            x.current_state() == SessionState.RUNNING
            for x in self.hosts + [f.session for f in self.far])
        while not running():
            if self.server.frames_served >= limit:
                raise RuntimeError(
                    f"sessions still synchronising after {limit} frames")
            self._serve_one()
        self.scalars["sync_frames"] = self.server.frames_served
        self.scalars["remote_endpoints"] = live * remote
        self.play_from = self.net.now
        for _ in range(int(ctx.traffic["warmup_frames"])):
            self._serve_one()
        self._block()
        mark("sessions_running")

    def window(self, seconds: float, pause_at=None, pause=None) -> float:
        out = super().window(seconds, pause_at, pause)
        if self.program_metrics is not None:
            # What a dispatch stages (ints, bits, the [S, B, F, P] branch
            # bits): the accepted metric that reads the series lists one
            # cell, so a traced run's scalars carry it.
            staged = self.program_metrics.series.get("tick_stage_bytes")
            if staged:
                self.scalars["tick_stage_bytes"] = float(staged[-1])
        return out

    def _counters(self) -> dict:
        out = super()._counters()
        # Every end of every lobby: the hosted sessions' exact counter and
        # the events the far ends' sessions raised.
        out["desync_events"] = int(
            self.host_metrics.counters.get("desyncs_flagged", 0)
            + sum(f.desyncs for f in self.far))
        out["match_frames_advanced"] = int(self._frames().sum())
        return out

    # -- after the window -----------------------------------------------

    def _drain(self) -> None:
        """``match_server_p2p``'s drain over a lobby's ``P - 1`` far ends:
        every input in flight lands (nobody advances), then one more served
        frame."""
        settled = lambda k: (  # noqa: E731
            self.hosts[k].confirmed_frame() >= min(
                [self.hosts[k].current_frame]
                + [f.session.current_frame for f in self.far_of[k]]) - 1)
        for _ in range(4 * self.fps):
            self.net.advance(self.dt)
            for core in self.server.groups:
                core.flush_reports()
            for host in self.hosts:
                host.poll_remote_clients()
            for far in self.far:
                far.poll()
            if all(settled(k) for k in self.live):
                break
        self.net.advance(self.dt)
        self.server.run_frame()
        self._block()

    def check(self) -> List[Comparison]:
        out = super().check()
        if not out[-1].name.startswith("reference."):
            return out      # the compared frame left a ring: nothing replayed
        # The shifted table is a model of what a session does with a delay:
        # held to the confirmed inputs every hosted session still has.
        window = int(self.ctx.config["settings"]["max_prediction"])
        upto = {k: min(self.hosts[k].confirmed_frame() + 1,
                       self.hosts[k].current_frame - 1) for k in self.live}
        table = self.keys.table(max(upto.values()))
        compared = differ = 0
        for k, host in ((k, self.hosts[k]) for k in self.live):
            for f in range(max(0, upto[k] - window), upto[k]):
                for h in range(self.players):
                    got = host.confirmed_input(h, f)
                    if got is not None:
                        compared += 1
                        differ += not np.array_equal(got, table[k, h, f])
        out.append(Comparison("guarantee.inputs_differ_from_shifted_table",
                              float(differ if compared else 1), 0))
        self.scalars["inputs_compared"] = compared
        return out

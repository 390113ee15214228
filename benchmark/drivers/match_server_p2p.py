"""Loop kind ``match_server_p2p``: what an operator runs behind a network.

One ``MatchServer`` of one title whose every match is a hosted P2P session
(``SessionBuilder(...).start_p2p_session(socket, clock=...)`` admitted by
``MatchServer.add_match(session, local_inputs)``): player 0 local on the
server, player 1 a remote client. All links share one ``LoopbackNetwork``
on a virtual clock that moves 1/fps a served frame; each far end's socket
is the program's ``ChaosSocket`` with that link's ``LossBurst`` plan.

Closed loop: per served frame the clock moves, every far end ticks (poll,
local input, ``advance_frame()``; a serial ``RollbackRunner`` only in the
sampled matches), then ``server.run_frame()``. The far ends stand for other
machines: their host time is measured and taken out of the window as the
profiler's pause is. A frame a session withholds by back-pressure
(``PredictionThreshold``) is neither a match-frame nor a failure:
``attempted = advanced + withheld + failed``.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

from benchmark.bursts import LossBursts
from benchmark.drivers.common import (
    Comparison, Context, limits_of, reference_gaps, tree_equal,
)
from benchmark.drivers.match_server import Driver as MatchServerDriver
from benchmark.inputs import HeldKeys, network_seed
from bevy_ggrs_tpu.session.common import (
    EventKind, PredictionThreshold, SessionState,
)


class FarEnd:
    """A remote client of one match, paced as ``GGRSStage`` paces one: a
    frame a tick, a tenth slower while the session says it is ahead (the
    ggrs time sync). With no ``runner`` its requests are discarded."""

    def __init__(self, session, feed, runner=None):
        self.session = session
        self.feed = feed
        self.runner = runner
        self.owed = 0.0          # frames of time not yet spent on a step
        self.slow = False
        self.skipped = 0         # PredictionThreshold on this end
        self.disconnects = 0

    def poll(self) -> None:
        self.session.poll_remote_clients()
        for ev in self.session.events():
            self.disconnects += ev.kind == EventKind.DISCONNECTED

    def tick(self) -> None:
        session = self.session
        self.poll()
        self.owed += 1.0
        cost = 1.1 if self.slow else 1.0
        if self.owed < cost:
            return
        self.owed -= cost
        if session.current_state() != SessionState.RUNNING:
            return
        self.slow = session.frames_ahead() > 0
        try:
            for h in session.local_player_handles():
                session.add_local_input(h, self.feed(session.current_frame, h))
            requests = session.advance_frame()
        except PredictionThreshold:
            self.skipped += 1
            return
        if self.runner is not None:
            self.runner.handle_requests(requests, session)


class Driver(MatchServerDriver):
    def __init__(self, ctx: Context):
        super().__init__(ctx)
        self.fps = int(ctx.config["settings"]["fps"])
        self.dt = 1.0 / self.fps
        self.play_from = float("inf")   # virtual time the handshakes ended
        self.withheld = 0               # frames withheld inside the window

    # -- set-up ---------------------------------------------------------

    def _session(self, me: int, k: int, metrics=None):
        """One end of match ``k``: handle ``me`` local at ("srv"|"ext", k),
        the other remote; every knob the configuration's."""
        from bevy_ggrs_tpu.session import PlayerType, SessionBuilder

        s = self.ctx.config["settings"]
        names = ("srv", "ext")
        builder = (
            SessionBuilder(self.ctx.title.input_spec())
            .with_num_players(self.players)
            .with_max_prediction_window(int(s["max_prediction"]))
            .with_input_delay(int(s["input_delay"]))
            .with_fps(self.fps)
            .with_disconnect_timeout(float(s["disconnect_timeout_s"]))
            .with_desync_detection(s["desync_detection"])
        )
        for h in range(self.players):
            builder.add_player(
                PlayerType.local() if h == me
                else PlayerType.remote((names[h], k)), h)
        return builder.start_p2p_session(
            self.net.socket((names[me], k)), clock=lambda: self.net.now,
            metrics=metrics)

    def setup(self, mark=lambda name: None) -> None:
        from bevy_ggrs_tpu.chaos import ChaosSocket
        from bevy_ggrs_tpu.serve.server import MatchServer
        from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
        from bevy_ggrs_tpu.utils.metrics import Metrics

        ctx, s = self.ctx, self.ctx.config["settings"]
        occ, netp = ctx.traffic["occupancy"], ctx.traffic["network"]
        live = int(occ["live"])
        if self.players != 2 or int(occ["admit"]) != live \
                or live > self.capacity:
            raise ValueError("occupancy does not fit the configuration")
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
        self.keys = HeldKeys(ctx.seed, live, self.players,
                             ctx.traffic["inputs"])
        self.keys.table(int(ctx.traffic["inputs_horizon_frames"]))
        self.net = LoopbackNetwork(
            latency=float(netp["latency_frames"]) * self.dt,
            jitter=float(netp["jitter_frames"]) * self.dt,
            loss=float(netp["loss"]), seed=network_seed(ctx.seed))
        self.bursts = LossBursts(ctx.seed, ctx.traffic["bursts"])
        rng = np.random.Generator(np.random.PCG64([ctx.seed, 0xC0FFEE]))
        self.sample = sorted(int(k) for k in rng.choice(
            live, size=min(int(ctx.traffic["sample_slots"]), live),
            replace=False))
        # Exact counters of the sessions (one dict add each): desyncs and
        # compared ballots. A session-only far end files a ballot for every
        # checksum it hears and compares none, so it gets no sink.
        self.host_metrics, self.far_metrics = Metrics(), Metrics()
        self.hosts, self.far = [], []
        self.live = {}
        for k in range(live):
            sampled = k in self.sample
            host = self._session(0, k, self.host_metrics)
            far = self._session(1, k, self.far_metrics if sampled else None)
            far.socket = ChaosSocket(
                far.socket, self.bursts.plan(k, self.dt),
                clock=self._burst_clock, addr=("ext", k))
            runner = None
            if sampled:
                runner = self._oracle()
                runner.warmup()
            self.hosts.append(host)
            self.far.append(FarEnd(far, self._feed(k), runner))
            self.live[k] = self.server.add_match(host, self._feed(k))
        mark("matches_admitted")
        # The serial oracle of the check: built and warmed here, so that the
        # check after the window neither traces nor compiles.
        self.oracle = self._oracle()
        self.oracle.warmup()
        mark("oracle_warm")
        # The sync handshakes, then served frames enough that rollbacks,
        # commits and a checksum exchange happened before the window.
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
        self.play_from = self.net.now
        for _ in range(int(ctx.traffic["warmup_frames"])):
            self._serve_one()
        self._block()
        mark("sessions_running")

    def _burst_clock(self) -> float:
        """The bursts' clock starts when play does: a handshake held up by
        a burst would only delay the window, which waits for all of them."""
        return self.net.now - self.play_from

    def _block(self) -> None:
        super()._block()
        self._block_far()

    def _block_far(self) -> None:
        import jax

        jax.block_until_ready(
            [f.runner.state for f in self.far if f.runner is not None])

    def _far_ends(self) -> float:
        """Every far end's tick; the host seconds it took. The sampled
        far ends' device work is waited for here, so it is theirs."""
        t = time.perf_counter()
        with self.ctx.annotate("bench/far_end"):
            for f in self.far:
                f.tick()
            self._block_far()
        return time.perf_counter() - t

    def _count_withheld(self, before) -> np.ndarray:
        """The benchmark's own count of the frames the last served frame
        withheld, whatever the program counts: a match that stands where it
        stood, whose session is RUNNING and more than the window past its
        last confirmed input (what ``advance_frame()`` raised
        ``PredictionThreshold`` on: nothing moved either number since)."""
        now = self._frames()
        if before is None:      # no frame served yet
            return now
        for j in np.flatnonzero(now == before):
            host = self.hosts[self._live_ks[j]]
            self.withheld += (
                host.current_state() == SessionState.RUNNING
                and host.current_frame - host.confirmed_frame()
                > host.max_prediction)
        return now

    def _serve_one(self) -> None:
        self.net.advance(self.dt)
        self._far_ends()
        self.server.run_frame()

    # -- the measured window --------------------------------------------

    def window(self, seconds: float, pause_at=None, pause=None) -> float:
        """Served frames back to back for ``seconds`` of the server's own
        time: the far ends' time, and that of ``pause()`` (the traced run
        stops its profiler there, ``pause_at`` seconds of wall clock into
        the loop), are taken out of the window."""
        server, annotate, net = self.server, self.ctx.annotate, self.net
        spans = self.series["serve_frame_ms"]
        self.open_counters()
        self._live_ks = list(self.live)
        self.withheld = 0
        frames0, before = self._frames(), None
        served0 = server.frames_served
        far_s, skipped0 = 0.0, sum(f.skipped for f in self.far)
        t0 = started = time.perf_counter()
        while True:
            ts = time.perf_counter()
            if ts - t0 >= seconds:
                break
            # ``pause_at`` is wall clock, the far ends' time included: what
            # fills the profiler's buffer is everything the traced stretch
            # holds, and the trace's own window is then ``pause_at`` long.
            if pause is not None and ts - started >= pause_at:
                self._block()
                pause()
                pause = None
                t0 += time.perf_counter() - ts
            # Between two served frames, outside the window: the count of
            # what the last one withheld, the clock, the far ends.
            tb = time.perf_counter()
            before = self._count_withheld(before)
            net.advance(self.dt)
            self._far_ends()
            spent = time.perf_counter() - tb
            far_s += spent
            t0 += spent
            ts = time.perf_counter()
            with annotate("bench/run_frame"):
                server.run_frame()
            spans.append((time.perf_counter() - ts) * 1e3)
        with annotate("bench/final_wait"):
            self._block()
        end = time.perf_counter()
        self.close_counters()
        self._count_withheld(before)
        served = server.frames_served - served0
        self.advanced = self._frames() - frames0
        self.attempted = served * len(self.live)
        self.failed = int(
            self.attempted - self.advanced.sum() - self.withheld)
        self.scalars.update({
            "match_frames": int(self.advanced.sum()),
            "frames_served": served,
            "frames_withheld": int(self.withheld),
            "far_end_s": far_s,
            "far_end_frames_skipped":
                sum(f.skipped for f in self.far) - skipped0,
            "live_matches": len(self.live),
            "virtual_frames": round(net.now * self.fps),
        })
        if spans:
            self.scalars["slowest_frame"] = {
                "index": int(np.argmax(spans)), "ms": float(max(spans))}
        if self._burst_clock() * self.fps > self.bursts.horizon:
            raise RuntimeError("the run outlasted the bursts' horizon_frames")
        return end - t0

    def _counters(self) -> dict:
        sv = self.server
        tot = lambda name: sum(getattr(g, name) for g in sv.groups)  # noqa
        both = lambda name: int(  # noqa: E731
            self.host_metrics.counters.get(name, 0)
            + self.far_metrics.counters.get(name, 0))
        out = dict(
            super()._counters(),
            desync_events=both("desyncs_flagged"),
            checksum_ballots=both("checksum_ballots"),
        )
        # Counters a program older than PR 27 does not keep are left out,
        # and with them the metrics that read them.
        if hasattr(sv, "frames_withheld_total"):
            out["frames_withheld"] = sv.frames_withheld_total
            out["match_frames_attempted"] = sv.frames_served * len(self.live)
        if hasattr(sv.groups[0], "burst_steps_total"):
            out["burst_steps_total"] = tot("burst_steps_total")
            out["burst_step_slots_total"] = tot("burst_step_slots_total")
        return out

    # -- after the window -----------------------------------------------

    def _drain(self) -> None:
        """``p2p_pair``'s drain recipe on every match: let every input in
        flight land (nobody advances), then one more served frame, after
        which a match's snapshot of frame ``confirmed + 1`` rests on
        confirmed inputs only. A host is never 8 frames ahead of its far
        end (the far end slows only while it leads), so that last frame is
        not withheld."""
        settled = lambda k: (  # noqa: E731
            self.hosts[k].confirmed_frame() >= min(
                self.hosts[k].current_frame,
                self.far[k].session.current_frame) - 1)
        for _ in range(4 * self.fps):
            self.net.advance(self.dt)
            for core in self.server.groups:
                core.flush_reports()
            for host, far in zip(self.hosts, self.far):
                host.poll_remote_clients()
                far.poll()
            if all(settled(k) for k in self.live):
                break
        self.net.advance(self.dt)
        self.server.run_frame()
        self._block()

    def check(self) -> List[Comparison]:
        from bevy_ggrs_tpu.schedule import CONFIRMED
        from bevy_ggrs_tpu.session.requests import AdvanceFrame, SaveGameState
        from bevy_ggrs_tpu.state import ring_load

        sv, ks = self.server, list(self.live)
        self._drain()
        totals = self._counters()
        disconnects = sum(f.disconnects for f in self.far) + sum(
            len(h.state_dict()["disconnected"]) for h in self.hosts)
        # The newest snapshot that rests on confirmed inputs only: frame
        # confirmed + 1, or where the far end leads (every input of the
        # host's frames is then confirmed) the last frame the host saved.
        upto = np.asarray([min(self.hosts[k].confirmed_frame() + 1,
                               self.hosts[k].current_frame - 1) for k in ks])
        depth = sv.groups[0].ring_depth
        ring_frames = [np.asarray(g.rings.frames) for g in sv.groups]
        idx = [(self.live[k].group, self.live[k].slot) for k in ks]
        rows = upto % depth
        left = sum(int(ring_frames[g][sl, r] != f)
                   for (g, sl), r, f in zip(idx, rows, upto))
        out = [
            Comparison("guarantee.desync_events", totals["desync_events"], 0),
            Comparison("guarantee.no_ballot_compared",
                       float(self._delta["checksum_ballots"] <= 0), 0),
            Comparison("guarantee.matches_advanced_nothing",
                       int((self.advanced <= 0).sum()), 0),
            Comparison("guarantee.match_frames_failed", float(self.failed), 0),
            # The program's counter against the benchmark's own count.
            Comparison("guarantee.withheld_frames_miscounted",
                       abs(self._delta.get("frames_withheld", self.withheld)
                           - self.withheld), 0),
            Comparison("guarantee.slot_faults", sv.faults_total, 0),
            Comparison("guarantee.quarantined",
                       sv.slots_quarantined + sv.slots_recovering, 0),
            Comparison("guarantee.evictions", sv.evictions_total, 0),
            Comparison("guarantee.disconnects", disconnects, 0),
            Comparison("guarantee.confirmed_frame_left_ring", left, 0),
        ]
        if left:
            return out

        # Sampled matches against a serial replay of the confirmed inputs,
        # bitwise, at frame confirmed + 1.
        table = self.keys.table(int(upto.max()))
        status = np.full((self.players,), CONFIRMED, np.int32)
        burst_frames = int(self.ctx.config["settings"]["max_prediction"])
        differ = 0
        for k in self.sample:
            oracle, n = self.oracle, int(upto[ks.index(k)])
            oracle.restore_state(0, self.initial)
            for f0 in range(0, n, burst_frames):
                burst = []
                for f in range(f0, min(f0 + burst_frames, n)):
                    burst += [SaveGameState(f), AdvanceFrame(
                        bits=np.ascontiguousarray(table[k, :, f]),
                        status=status)]
                oracle.handle_requests(burst)
            h = self.live[k]
            served = ring_load(sv.groups[h.group].slot_ring(h.slot), n)
            differ += int(not tree_equal(served, oracle.state))
        out.append(Comparison(
            "guarantee.sampled_matches_differ_from_serial_replay", differ, 0))

        # Every live match at frame confirmed + 1 against the plain
        # reference replaying both players' generated inputs.
        want_t, want_v, want_frames = self.ctx.reference.replay(
            table[ks][:, :, :int(upto.max())], upto)
        got = [self.ctx.title.readback(g.rings.states, self.players)
               for g in sv.groups]
        pick = lambda j: np.stack(  # noqa: E731
            [got[g][j][sl, r] for (g, sl), r in zip(idx, rows)])
        limits = limits_of(self.ctx.config)
        out.append(Comparison(
            "reference.frame_count_gap",
            float(np.abs(pick(2).astype(np.int64)
                         - want_frames.astype(np.int64)).max()), 0))
        out += [Comparison(name, gap, limits[name]) for name, gap in
                reference_gaps(pick(0), pick(1), want_t, want_v)]
        self.scalars["checked_matches"] = len(ks)
        self.scalars["checked_frames_each"] = [int(upto.min()),
                                               int(upto.max())]
        return out

    def cost_shapes(self) -> dict:
        """As ``match_server``'s, without a SyncTest check distance."""
        import jax

        core = self.server.groups[0]
        nbytes = lambda tree: int(sum(  # noqa: E731
            x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree)))
        return {
            "slot_states_bytes": nbytes(core.states),
            "slot_rings_bytes": nbytes(core.rings),
            "spec_states_bytes": nbytes(core.prev_states),
            "spec_rings_bytes": nbytes(core.prev_rings),
            "ring_depth": int(core.ring_depth),
        }

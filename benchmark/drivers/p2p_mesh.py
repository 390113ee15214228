"""Loop kind ``p2p_mesh``: a lobby. ``p2p_pair`` with N peers in a full mesh,
an input delay and a live spectator.

Player ``h`` is local to peer ``h``; all sessions share this one process and
the program's loopback transport on a virtual network clock. Peer 0 is the
client under test: it speculates, it feeds the spectator (its session adds
``PlayerType.spectator``), and it alone is timed. Peer 1 resimulates every
rollback and reports checksums, so ballots are compared: through a plain
``RollbackRunner`` app, or, where the configuration gives it
``peer1_speculation_branches``, through the packed fused tick of that many
branches (a third of the serial executor's host cost). Peers 2 .. N-1 are
session-only far ends (``match_server_p2p.FarEnd``): they feed their
player's inputs and discard their requests. The spectator is a
``SessionType.SPECTATOR`` app of its own, updated every
``spectator_tick_every``-th loop tick at that fraction of the frame rate
(one step an update; its session's catch-up does the rest).

The window loop is ``p2p_pair``'s, unchanged: what it calls its far end is
here everything that is not peer 0 (``_Others``), ticked inside
``bench/far_end`` and timed part by part. The spectator's distance behind
peer 0 is the own series ``spectator_lag_frames``.

With an input delay ``d`` a session commits what a player holds at frame
``f`` to frame ``f + d``, so the inputs the simulation used are the
generator's table **shifted**: frame ``f`` of player ``h`` is what ``h`` held
at ``f - d``, frames ``0 .. d-1`` blank. The serial oracle and the plain
reference replay that table; ``check()`` also holds it to the confirmed
inputs peer 0's session still has.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

from benchmark.drivers.common import (
    Comparison, Context, limits_of, reference_gaps, tree_equal,
)
from benchmark.drivers.match_server_p2p import FarEnd
from benchmark.drivers.p2p_pair import Driver as P2PPairDriver
from benchmark.inputs import HeldKeys, network_seed
from bevy_ggrs_tpu.session.common import EventKind, SessionState

# Parts of a loop tick that are not peer 0's, each a series of host ms.
PARTS = ("peer1_ms", "far_ends_ms", "spectator_ms", "others_ms")
OWN_SERIES = PARTS + ("spectator_lag_frames",)


def shifted_table(table: np.ndarray, delay: int) -> np.ndarray:
    """``[P, F]`` as the simulation used it under an input delay."""
    out = np.zeros_like(table)
    if delay < table.shape[-1]:
        out[..., delay:] = table[..., :table.shape[-1] - delay]
    return out


class _Others:
    """Everything of the lobby that is not peer 0, under the three names
    ``p2p_pair``'s window loop uses of its far end: ``update(now=)``,
    ``stage.runner.state`` and ``frame``."""

    def __init__(self, driver: "Driver"):
        self._d = driver
        self.stage = self.runner = self
        self.ticks = 0

    @property
    def frame(self) -> int:
        return self._d.peer1.frame

    @property
    def state(self):
        """Of every device-resident runner that is not peer 0's."""
        d = self._d
        return tuple(app.stage.runner.state
                     for app in [d.peer1] + d.spectator_apps)

    def update(self, now: float) -> None:
        d = self._d
        series = d.series
        t0 = time.perf_counter()
        d.peer1.update(now=now)
        t1 = time.perf_counter()
        for far in d.far_ends:
            far.tick()
        t2 = time.perf_counter()
        self.ticks += 1
        for app in d.spectator_apps:
            if self.ticks % d.spectator_every == 0:
                app.update(now=now)
            series["spectator_lag_frames"].append(
                float(d.a.frame - app.frame))
        t3 = time.perf_counter()
        series["peer1_ms"].append((t1 - t0) * 1e3)
        series["far_ends_ms"].append((t2 - t1) * 1e3)
        series["spectator_ms"].append((t3 - t2) * 1e3)
        series["others_ms"].append((t3 - t0) * 1e3)


class _CountingFarEnd(FarEnd):
    """``FarEnd`` that also counts the desyncs its session flags."""

    desyncs = 0

    def poll(self) -> None:
        self.session.poll_remote_clients()
        for ev in self.session.events():
            self.disconnects += ev.kind == EventKind.DISCONNECTED
            self.desyncs += ev.kind == EventKind.DESYNC_DETECTED


class Driver(P2PPairDriver):
    def __init__(self, ctx: Context):
        super().__init__(ctx)
        s = ctx.config["settings"]
        self.delay = int(s["input_delay"])
        self.num_spectators = int(s["spectators"])
        self.spectator_every = int(s.get("spectator_tick_every", 1))
        for name in OWN_SERIES:
            self.series[name] = []
        if self.players < 3:
            raise ValueError("a mesh has a timed peer, a resimulating peer "
                             "and at least one session-only far end")

    # -- set-up ---------------------------------------------------------

    def _builder(self):
        from bevy_ggrs_tpu.session import SessionBuilder

        s = self.ctx.config["settings"]
        return (
            SessionBuilder(self.ctx.title.input_spec())
            .with_num_players(self.players)
            .with_max_prediction_window(self.window_frames)
            .with_input_delay(self.delay)
            .with_fps(self.fps)
            .with_desync_detection(s["desync_detection"])
        )

    def _plugin(self, input_system, fps=None):
        return (
            self.ctx.title.build_plugin(self.players, self.ctx.control)
            .with_update_frequency(fps or self.fps)
            .with_input_system(input_system)
            .with_max_prediction_window(self.window_frames)
            .with_clock(lambda: self.net.now)
        )

    def setup(self, mark=lambda name: None) -> None:
        import jax

        from bevy_ggrs_tpu.app import SessionType
        from bevy_ggrs_tpu.session import PlayerType
        from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
        from bevy_ggrs_tpu.utils.metrics import Metrics

        ctx, s = self.ctx, self.ctx.config["settings"]
        netp = ctx.traffic["network"]
        self.net = LoopbackNetwork(
            latency=float(netp["latency_frames"]) * self.dt,
            jitter=float(netp["jitter_frames"]) * self.dt,
            loss=float(netp["loss"]), seed=network_seed(ctx.seed),
        )
        clock = lambda: self.net.now  # noqa: E731
        self.keys = HeldKeys(ctx.seed, 1, self.players, ctx.traffic["inputs"])
        self.keys.table(int(ctx.traffic["inputs_horizon_frames"]))
        # One sink for peer 0's session and runner in the traced run (the
        # session's span ``spectator_fanout`` is a series of it); untraced,
        # the session's exact counters alone.
        if ctx.trace:
            self.program_metrics = Metrics()
        self.session_metrics = self.program_metrics or Metrics()

        def input_system(handle, app):
            return self.keys.bits(0, app.session.current_frame, handle)

        def feed(frame, handle):
            return self.keys.bits(0, frame, handle)

        spectator_addrs = [("spec", i) for i in range(self.num_spectators)]
        self.sessions = []
        for me in range(self.players):
            builder = self._builder()
            for h in range(self.players):
                builder.add_player(
                    PlayerType.local() if h == me
                    else PlayerType.remote(("peer", h)), h)
            if me == 0:
                for addr in spectator_addrs:
                    builder.add_player(PlayerType.spectator(addr),
                                       self.players)
            self.sessions.append(builder.start_p2p_session(
                self.net.socket(("peer", me)), clock=clock,
                metrics=self.session_metrics if me == 0 else None))
        self.apps = []
        for me in range(2):
            plugin = self._plugin(input_system)
            if me == 0:
                plugin.with_speculation(int(s["speculation_branches"]))
                if self.program_metrics is not None:
                    plugin.with_metrics(self.program_metrics)
            else:
                plugin.with_speculation(
                    int(s.get("peer1_speculation_branches", 0)))
            app = plugin.build()
            app.insert_session(self.sessions[me], SessionType.P2P)
            self.apps.append(app)
        self.a, self.peer1 = self.apps
        self.far_ends = [_CountingFarEnd(x, feed) for x in self.sessions[2:]]
        self.spectator_apps = []
        for addr in spectator_addrs:
            app = self._plugin(
                input_system, self.fps // self.spectator_every).build()
            app.insert_session(
                self._builder().start_spectator_session(
                    ("peer", 0), self.net.socket(addr), clock=clock),
                SessionType.SPECTATOR)
            self.spectator_apps.append(app)
        mark("peers_built")
        self.b = _Others(self)
        self.runner = self.a.stage.runner
        if self.runner.spec_frames != int(s["speculation_frames"]):
            raise RuntimeError("speculation depth is not the configuration's")
        self.scalars["rollout_device_ms"] = self.runner.rollout_device_ms
        self.scalars["extra_call_ms"] = self.runner.extra_call_ms
        self.scalars["tick_split"] = bool(getattr(self.runner, "_split", 0))
        self.oracle = (
            ctx.title.build_plugin(self.players, ctx.control)
            .with_input_system(input_system)
            .with_max_prediction_window(self.window_frames)
        ).build().stage.runner
        mark("oracle_built")

        # Warm-up on the virtual clock, unpaced: until every handshake of
        # the mesh and the spectator's is done, then the mix's ticks.
        everyone = self.sessions + [x.session for x in self.spectator_apps]
        running = lambda: all(  # noqa: E731
            x.current_state() == SessionState.RUNNING for x in everyone)
        limit = int(ctx.traffic["warmup_ticks"]) * 4
        ticks = 0
        while not running():
            if ticks >= limit:
                raise RuntimeError(
                    f"sessions still synchronising after {limit} ticks")
            self._tick_both()
            ticks += 1
        self.scalars["sync_ticks"] = ticks
        for _ in range(int(ctx.traffic["warmup_ticks"])):
            self._tick_both()
        if self.a.frame == 0 or not running():
            raise RuntimeError("peers did not advance during warm-up")
        for name in OWN_SERIES:
            self.series[name].clear()
        jax.block_until_ready((self.runner.state, self.b.state))
        mark("sessions_running")

    def _tick_both(self) -> None:
        self.net.advance(self.dt)
        self.a.update(now=self.net.now)
        self.b.update(now=self.net.now)

    def _counters(self) -> dict:
        out = super()._counters()
        out["far_frame_min"] = min(
            [self.peer1.frame] + [x.current_frame for x in self.sessions[2:]])
        out["spectator_frame_min"] = min(
            [x.frame for x in self.spectator_apps], default=self.a.frame)
        return out

    # -- after the window -----------------------------------------------

    def _drain(self) -> None:
        """``p2p_pair``'s drain recipe over the mesh: every input in flight
        lands, then peer 0 takes one more step, after which its snapshots
        up to ``confirmed + 1`` rest on confirmed inputs only."""
        a, net = self.a, self.net
        apps = self.apps + self.spectator_apps
        for _ in range(4 * self.fps):
            net.advance(self.dt)
            for app in apps:
                flush = getattr(app.stage.runner, "flush_reports", None)
                if flush is not None:
                    flush(app.session)
                app.session.poll_remote_clients(net.now)
                app.events.extend(app.session.events())
            for far in self.far_ends:
                far.poll()
            frames = [a.frame, self.peer1.frame] + [
                x.current_frame for x in self.sessions[2:]]
            if a.session.confirmed_frame() >= min(frames) - 1:
                break
        # One update is one step only for a stage at full speed: one that is
        # yielding to slower peers (``run_slow``) may let a frame's time pass
        # without stepping, and the rollback would stay unexecuted.
        a.stage.last_time = net.now
        frame = a.frame
        for _ in range(4):
            net.advance(self.dt)
            a.update(now=net.now)
            if a.frame > frame:
                break

    def _replay(self, table: np.ndarray, frames: int):
        """The serial oracle moved on to ``frames`` (it only goes
        forward), through the shifted table."""
        from bevy_ggrs_tpu.schedule import CONFIRMED
        from bevy_ggrs_tpu.session.requests import AdvanceFrame, SaveGameState

        status = np.full((self.players,), CONFIRMED, np.int32)
        f0 = self.oracle.frame
        while f0 < frames:
            f1 = min(f0 + self.window_frames, frames)
            burst = []
            for f in range(f0, f1):
                burst += [SaveGameState(f),
                          AdvanceFrame(bits=np.ascontiguousarray(table[:, f]),
                                       status=status)]
            self.oracle.handle_requests(burst)
            f0 = f1
        return self.oracle.state

    def _against(self, state, table, frames: int) -> List[tuple]:
        """One simulation at ``frames`` against the oracle (moved there) and
        the plain reference: ``(name, value)`` rows under ``p2p_pair``'s
        names, bitwise first."""
        differs = float(not tree_equal(state, self._replay(table, frames)))
        got_t, got_v, got_n = self.ctx.title.readback(state, self.players)
        want_t, want_v, want_n = self.ctx.reference.replay(
            table[None, :, :frames], np.asarray([frames]))
        return [("guarantee.state_differs_from_serial_replay", differs),
                ("reference.frame_count_gap",
                 float(abs(int(got_n) - int(want_n[0]))))
                ] + reference_gaps(got_t, got_v, want_t[0], want_v[0])

    def check(self) -> List[Comparison]:
        from bevy_ggrs_tpu.state import ring_load

        a, runner = self.a, self.runner
        self._drain()
        apps = self.apps + self.spectator_apps
        desyncs = sum(1 for app in apps for ev in app.events
                      if ev.kind == EventKind.DESYNC_DETECTED)
        desyncs += sum(far.desyncs for far in self.far_ends)
        disconnects = sum(1 for app in apps for ev in app.events
                          if ev.kind == EventKind.DISCONNECTED)
        disconnects += sum(far.disconnects for far in self.far_ends)
        self.failed += desyncs + disconnects
        # With an input delay the confirmed frame can pass the newest
        # snapshot: the frame compared is the newest one both confirmed
        # and held.
        ring_frames = np.asarray(runner.ring.frames)
        upto = min(a.session.confirmed_frame() + 1, int(ring_frames.max()))
        in_ring = upto in ring_frames and upto > self.delay
        attestation = runner.attestation
        d = self._delta
        out = [
            Comparison("guarantee.desync_events", desyncs, 0),
            Comparison("guarantee.disconnect_events", disconnects, 0),
            Comparison("guarantee.no_ballot_compared",
                       float(d["checksum_ballots"] <= 0), 0),
            Comparison("guarantee.a_peer_did_not_advance",
                       float(min(d["peer0_frame"], d["far_frame_min"],
                                 d["spectator_frame_min"]) <= 0), 0),
            Comparison("guarantee.speculation_off",
                       float(not (attestation is not None and attestation.ok
                                  and runner.speculation_enabled)), 0),
            Comparison("guarantee.confirmed_frame_left_ring",
                       float(not in_ring), 0),
        ]
        if not in_ring:
            return out

        horizon = max([upto] + [x.frame for x in self.spectator_apps])
        table = shifted_table(self.keys.table(horizon)[0], self.delay)
        # The table is a model of what the session does with a delay: held
        # to the confirmed inputs peer 0's session still has.
        compared = differ = 0
        for f in range(max(0, upto - self.window_frames), upto):
            for h in range(self.players):
                got = a.session.confirmed_input(h, f)
                if got is not None:
                    compared += 1
                    differ += not np.array_equal(got, table[h, f])
        out.append(Comparison("guarantee.inputs_differ_from_shifted_table",
                              float(differ if compared else 1), 0))

        # Spectators first: they are behind peer 0, and the oracle only
        # goes forward.
        sims = sorted(
            [(app.frame, f"spectator{i or ''}_", app.stage.runner.state)
             for i, app in enumerate(self.spectator_apps)]
            + [(upto, "", ring_load(runner.ring, upto))], key=lambda x: x[0])
        limits = limits_of(self.ctx.config)
        for frames, who, state in sims:
            # A spectator's rows: ``guarantee.spectator_...``.
            out += [Comparison(name.replace(".", "." + who, 1), value,
                               limits.get(name, 0))
                    for name, value in self._against(state, table, frames)]
        self.scalars["checked_frame"] = upto
        self.scalars["spectator_checked_frames"] = [
            app.frame for app in self.spectator_apps]
        return out

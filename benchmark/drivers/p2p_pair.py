"""Loop kind ``p2p_pair``: what a player's client runs.

Two peers of one title in this one process over the program's loopback
transport on a virtual network clock. Peer 0 is the client under test (it
speculates, and it alone is timed); peer 1 is the far end and resimulates
serially, so every checksum the two exchange also compares the speculating
executable with the serial one. Ticks are due every 1/fps of wall time
(open loop): a tick is timed from when it was due.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

from benchmark.drivers.common import (
    Comparison, Context, DriverBase, limits_of, reference_gaps, tree_equal,
    wait_until,
)
from benchmark.inputs import HeldKeys, network_seed


class Driver(DriverBase):
    def __init__(self, ctx: Context):
        super().__init__(ctx, ["frame_ms", "recovery_ms", "tick_late_ms"])
        s = ctx.config["settings"]
        self.players = int(s["num_players"])
        self.window_frames = int(s["max_prediction"])
        self.fps = int(s["fps"])
        self.dt = 1.0 / self.fps

    # -- set-up ---------------------------------------------------------

    def setup(self, mark=lambda name: None) -> None:
        from bevy_ggrs_tpu.app import SessionType
        from bevy_ggrs_tpu.session import PlayerType, SessionBuilder
        from bevy_ggrs_tpu.session.common import SessionState
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
        # Counters of the session are exact and cost one dict add each; the
        # runner's timers are read only in the traced run.
        self.session_metrics = Metrics()
        if ctx.trace:
            self.program_metrics = Metrics()

        def input_system(handle, app):
            return self.keys.bits(0, app.session.current_frame, handle)

        self.apps = []
        for me in range(2):
            plugin = (
                ctx.title.build_plugin(self.players, ctx.control)
                .with_update_frequency(self.fps)
                .with_input_system(input_system)
                .with_max_prediction_window(self.window_frames)
                .with_clock(clock)
            )
            if me == 0:
                plugin.with_speculation(int(s["speculation_branches"]))
                if self.program_metrics is not None:
                    plugin.with_metrics(self.program_metrics)
            app = plugin.build()
            builder = (
                SessionBuilder(ctx.title.input_spec())
                .with_num_players(self.players)
                .with_max_prediction_window(self.window_frames)
                .with_input_delay(int(s["input_delay"]))
                .with_fps(self.fps)
                .with_desync_detection(s["desync_detection"])
            )
            for h in range(self.players):
                builder.add_player(
                    PlayerType.local() if h % 2 == me
                    else PlayerType.remote(("peer", 1 - me)), h)
            session = builder.start_p2p_session(
                self.net.socket(("peer", me)), clock=clock,
                metrics=self.session_metrics if me == 0 else None,
            )
            app.insert_session(session, SessionType.P2P)
            self.apps.append(app)
        mark("peers_built")
        self.a, self.b = self.apps
        self.runner = self.a.stage.runner
        if self.runner.spec_frames != int(s["speculation_frames"]):
            raise RuntimeError("speculation depth is not the configuration's")
        # The serial oracle of the guarantee check: built and warmed here so
        # that the check after the window compiles nothing either.
        oracle_plugin = (
            ctx.title.build_plugin(self.players, ctx.control)
            .with_input_system(input_system)
            .with_max_prediction_window(self.window_frames)
        )
        self.oracle = oracle_plugin.build().stage.runner
        mark("oracle_built")

        # Warm-up on the virtual clock, unpaced: the handshake, then enough
        # ticks that rollbacks, speculative commits and checksum exchanges
        # have all happened once before the window opens.
        warm = int(ctx.traffic["warmup_ticks"])
        for _ in range(warm):
            self._tick_both()
        if any(app.session.current_state() != SessionState.RUNNING
               for app in self.apps) or self.a.frame == 0:
            raise RuntimeError("peers did not reach RUNNING during warm-up")
        import jax

        jax.block_until_ready((self.runner.state, self.b.stage.runner.state))

    def _tick_both(self) -> None:
        self.net.advance(self.dt)
        for app in self.apps:
            app.update(now=self.net.now)

    # -- the measured window --------------------------------------------

    def window(self, seconds: float, pause_at=None, pause=None) -> float:
        """Ticks due every 1/fps for ``seconds``. Once ``pause_at`` seconds
        have been measured, ``pause()`` runs between two ticks (the traced
        run stops its profiler there) and the time it took is taken out:
        the schedule of due times moves by it."""
        import jax

        a, b, net, runner = self.a, self.b, self.net, self.runner
        annotate = self.ctx.annotate
        frame_ms, recovery_ms, late_ms = (
            self.series["frame_ms"], self.series["recovery_ms"],
            self.series["tick_late_ms"])
        self.open_counters()
        stalled: List[float] = []  # due times of ticks that did not advance
        slowest = (0.0, 0, 0, 0.0, 0.0, 0.0)
        overslept, free_at = 0.0, 0.0
        dt = self.dt
        t0 = time.perf_counter()
        k = 0
        while k * dt < seconds:
            if pause is not None and k * dt >= pause_at:
                jax.block_until_ready((runner.state, b.stage.runner.state))
                t_pause = time.perf_counter()
                pause()
                pause = None
                t0 += max(0.0, time.perf_counter()
                          - max(t_pause, t0 + k * dt))
            due = t0 + k * dt
            with annotate("bench/pacer_sleep"):
                wait_until(due)
            start = time.perf_counter()
            late_ms.append((start - due) * 1e3)
            overslept = max(overslept, start - max(due, free_at))
            net.advance(dt)
            skipped0 = a.stage.frames_skipped
            rollbacks0 = runner.rollbacks_total
            with annotate("bench/update"):
                a.update(now=net.now)
            with annotate("bench/readable"):
                jax.block_until_ready(runner.state)
            ready = time.perf_counter()
            self.attempted += 1
            if a.stage.frames_skipped > skipped0:
                self.failed += 1
                stalled.append(due)
            else:
                frame_ms.append((ready - due) * 1e3)
                frame_ms.extend((ready - d) * 1e3 for d in stalled)
                stalled.clear()
            if runner.rollbacks_total > rollbacks0:
                recovery_ms.append((ready - start) * 1e3)
            with annotate("bench/far_end"):
                b.update(now=net.now)
            done = free_at = time.perf_counter()
            if done - start > slowest[0]:
                slowest = (done - start, k, a.frame, (start - due) * 1e3,
                           (ready - start) * 1e3, (done - ready) * 1e3)
            k += 1
        jax.block_until_ready((runner.state, b.stage.runner.state))
        end = time.perf_counter()
        # A tick still stalled when the window closes never got its frame.
        frame_ms.extend((end - d) * 1e3 for d in stalled)
        self.close_counters()
        self.scalars["ticks"] = k
        # For telling a stall of the host from one of the program: the tick
        # that worked longest, and the longest the pacer overslept.
        self.scalars["slowest_tick"] = dict(zip(
            ("tick", "frame", "late_ms", "peer0_ms", "far_end_ms"),
            slowest[1:]))
        self.scalars["worst_oversleep_ms"] = overslept * 1e3
        return end - t0

    def _counters(self) -> dict:
        r = self.runner
        return {
            "rollbacks_total": r.rollbacks_total,
            "spec_hits": r.spec_hits,
            "spec_partial_hits": r.spec_partial_hits,
            "spec_misses": r.spec_misses,
            "rollback_frames_recovered_total":
                r.rollback_frames_recovered_total,
            "rollback_frames_total": r.rollback_frames_total,
            "frames_skipped": self.a.stage.frames_skipped,
            "peer0_frame": self.a.frame,
            "peer1_frame": self.b.frame,
            "checksum_ballots":
                int(self.session_metrics.counters.get("checksum_ballots", 0)),
        }

    # -- after the window -----------------------------------------------

    def check(self) -> List[Comparison]:
        from bevy_ggrs_tpu.schedule import CONFIRMED
        from bevy_ggrs_tpu.session.common import EventKind
        from bevy_ggrs_tpu.session.requests import AdvanceFrame, SaveGameState
        from bevy_ggrs_tpu.state import ring_load

        a, b, net, runner = self.a, self.b, self.net, self.runner
        # chip_smoke.py's drain recipe: let every input in flight land, then
        # give peer 0 exactly one more step, after which its snapshot of
        # frame ``confirmed + 1`` rests on confirmed inputs only.
        for _ in range(4 * self.fps):
            net.advance(self.dt)
            for app in self.apps:
                flush = getattr(app.stage.runner, "flush_reports", None)
                if flush is not None:
                    flush(app.session)
                app.session.poll_remote_clients(net.now)
                app.events.extend(app.session.events())
            if a.session.confirmed_frame() >= min(a.frame, b.frame) - 1:
                break
        a.stage.last_time = net.now
        net.advance(self.dt)
        a.update(now=net.now)

        desyncs = sum(1 for app in self.apps for ev in app.events
                      if ev.kind == EventKind.DESYNC_DETECTED)
        self.failed += desyncs
        upto = a.session.confirmed_frame() + 1
        ring_frames = np.asarray(runner.ring.frames)
        in_ring = upto in ring_frames
        attestation = runner.attestation
        out = [
            Comparison("guarantee.desync_events", desyncs, 0),
            Comparison("guarantee.no_ballot_compared",
                       float(self._delta["checksum_ballots"] <= 0), 0),
            Comparison("guarantee.a_peer_did_not_advance",
                       float(min(self._delta["peer0_frame"],
                                 self._delta["peer1_frame"]) <= 0), 0),
            Comparison("guarantee.speculation_off",
                       float(not (attestation is not None and attestation.ok
                                  and runner.speculation_enabled)), 0),
            Comparison("guarantee.confirmed_frame_left_ring",
                       float(not in_ring), 0),
        ]
        if not in_ring:
            return out

        table = self.keys.table(upto)[0]            # [P, F]
        status = np.full((self.players,), CONFIRMED, np.int32)
        for f0 in range(0, upto, self.window_frames):
            burst = []
            for f in range(f0, min(f0 + self.window_frames, upto)):
                burst += [SaveGameState(f),
                          AdvanceFrame(bits=np.ascontiguousarray(table[:, f]),
                                       status=status)]
            self.oracle.handle_requests(burst)
        live = ring_load(runner.ring, upto)
        out.append(Comparison(
            "guarantee.state_differs_from_serial_replay",
            float(not tree_equal(live, self.oracle.state)), 0))

        got_t, got_v, got_frames = self.ctx.title.readback(live, self.players)
        want_t, want_v, want_frames = self.ctx.reference.replay(
            table[None, :, :upto], np.asarray([upto]))
        limits = limits_of(self.ctx.config)
        out.append(Comparison("reference.frame_count_gap",
                              float(abs(int(got_frames) - int(want_frames[0]))),
                              0))
        out += [Comparison(name, gap, limits[name]) for name, gap in
                reference_gaps(got_t, got_v, want_t[0], want_v[0])]
        self.scalars["checked_frame"] = upto
        return out

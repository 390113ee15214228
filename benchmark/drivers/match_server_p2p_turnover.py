"""Loop kind ``match_server_p2p_turnover``: a server whose matches end and
begin while it serves.

``match_server_p2p``'s server, sessions, network and far ends (its
``FarEnd``, session builder, input feed and serial oracle are used as they
are), with a lifecycle on top: the window opens on a full server and every
seat then holds one match after another, by the plan ``benchmark/
turnover.py`` draws from the seed. Tables are kept by MATCH ID (a seat's
generation ``g`` is match ``g * seats + seat``), never by slot.

The driver plays the operator with the server's public calls only. A
served frame of the window is:

- outside the window, as in ``server256.wan``: the benchmark's own count of
  what the last frame advanced or withheld, the virtual clock, every far
  end's tick (a far end whose match ended, or whose player dropped, ticks
  no more), and the far end of each arrival due now;
- inside it: the operator asks for every seat freed in the last frame
  again (``free_slot_handles()`` says which, a new host session and
  ``enqueue_match`` with an ``AdmissionTrace``), ``run_frame()``, then
  ``drain_events()``: a match whose remote player is ``DISCONNECTED`` has
  its result read and is retired (``retire_match``), and so is every match
  whose game is over in this frame. A result is the newest snapshot that
  rests on confirmed inputs (frame ``min(confirmed + 1, current - 1)`` of
  the slot's ring), read to the host: a deployment records who won.

``attempted = advanced + withheld + failed`` over the matches whose session
is RUNNING; a seat that is synchronising, or between two tenants, attempts
nothing. A program without ``MatchServer.drain_events`` is refused by
name, at once.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from benchmark.bursts import LossBursts
from benchmark.drivers.common import (
    Comparison, Context, limits_of, reference_gaps, tree_equal,
)
from benchmark.drivers.match_server_p2p import Driver as HostedP2PDriver
from benchmark.drivers.match_server_p2p import FarEnd
from benchmark.inputs import HeldKeys, network_seed
from benchmark.turnover import SILENT_DROP, Turnover
from bevy_ggrs_tpu.session.common import EventKind, SessionState

# A control of this loop kind alone (``--control``), beside the title's two:
# the operator lets the first reported drop sit in its slot.
LEAVE_ONE_DROP = "leave_one_drop"
# Served frames a report may trail the instant the timers name: the poll
# that notices, the supervisor's tick a frame later, float dust of a clock
# that adds 1/60 s a frame.
REPORT_SLACK_FRAMES = 12
NEVER = float("inf")


class _Tap:
    """The server's socket of a match whose player will drop: the served
    frame of the last datagram it delivered, kept by the benchmark beside
    the session's own timers."""

    def __init__(self, inner, frame_of):
        self.inner, self.addr = inner, inner.addr
        self._frame_of = frame_of
        self.last_frame: Optional[int] = None

    def send_to(self, data, addr) -> None:
        self.inner.send_to(data, addr)

    def receive_all(self):
        got = self.inner.receive_all()
        if got:
            self.last_frame = self._frame_of()
        return got

    def close(self) -> None:
        self.inner.close()


class _Tenant:
    """One match and what the operator knows of it."""

    def __init__(self, life, first: int):
        self.life = life
        self.first = first                  # served frame it was asked for
        self.given = life.seat              # the seat the server gave it
        self.end: Optional[int] = None      # served frame it ends in
        self.retired: Optional[int] = None
        self.host = self.far = self.handle = self.tap = None
        self.play_from = NEVER              # virtual time both ends ran
        self.running_at: Optional[int] = None
        self.silent = False                 # the far end ticks no more
        self.interrupted_at: Optional[int] = None
        self.disconnected_at: Optional[int] = None
        self.frozen_from: Optional[int] = None  # last remote input confirmed
        self.frame_seen = 0                 # slot frame at the last count
        self.advanced = 0                   # frames inside the window
        self.result = None                  # (frame, translation, velocity, count)
        self.ring = None                    # a sampled match's ring, at retirement


class Driver(HostedP2PDriver):
    def __init__(self, ctx: Context):
        super().__init__(ctx)
        # The operator's work of a served frame, and its two sides: asking
        # for the freed seats before run_frame(), events and ends after it.
        for name in ("operator_ms", "operator_ask_ms", "operator_end_ms"):
            self.series[name] = []
        self.frame = -1                     # the window's served frame
        self.counted = {"attempted": 0, "moved": 0, "withheld": 0,
                        "syncing": 0}

    # -- set-up ---------------------------------------------------------

    def setup(self, mark=lambda name: None) -> None:
        from bevy_ggrs_tpu.serve.server import MatchServer
        from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
        from bevy_ggrs_tpu.utils.metrics import Metrics

        if not hasattr(MatchServer, "drain_events"):
            raise RuntimeError(
                "loop kind match_server_p2p_turnover needs "
                "MatchServer.drain_events(): this program hands a hosted "
                "session's events to nobody")
        ctx, s = self.ctx, self.ctx.config["settings"]
        occ, netp = ctx.traffic["occupancy"], ctx.traffic["network"]
        seats = int(occ["live"])
        if self.players != 2 or int(occ["admit"]) != seats \
                or seats != self.capacity:
            raise ValueError("a turnover keeps every slot of the server taken")
        if ctx.trace:
            self.program_metrics = Metrics()
        control = None if ctx.control == LEAVE_ONE_DROP else ctx.control
        self.schedule = ctx.title.make_schedule(control)
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
        self.plan = Turnover(ctx.seed, seats, ctx.traffic["turnover"])
        self.keys = HeldKeys(ctx.seed, self.plan.matches, self.players,
                             ctx.traffic["inputs"])
        self.keys.table(int(ctx.traffic["inputs_horizon_frames"]))
        self.net = LoopbackNetwork(
            latency=float(netp["latency_frames"]) * self.dt,
            jitter=float(netp["jitter_frames"]) * self.dt,
            loss=float(netp["loss"]), seed=network_seed(ctx.seed))
        self.bursts = LossBursts(ctx.seed, ctx.traffic["bursts"])
        self.timeout_frames = round(float(s["disconnect_timeout_s"]) * self.fps)
        self.notify_frames = round(
            float(s["disconnect_notify_start_s"]) * self.fps)
        # The seats whose second tenant is compared bitwise: drawn from
        # those that change hands early. Its far end resimulates (a warmed
        # serial runner each) and reports checksums, so ballots are compared.
        early = [k for k in range(seats) if self.plan.first_end(k)
                 <= int(ctx.traffic["sample_changed_by_frame"])
                 and self.plan.life(k, 0).kind != SILENT_DROP]
        rng = np.random.Generator(np.random.PCG64([ctx.seed, 0xC0FFEE]))
        sample = rng.choice(
            early, size=min(int(ctx.traffic["sample_slots"]), len(early)),
            replace=False)
        self.host_metrics, self.far_metrics = Metrics(), Metrics()
        self.runners = {self.plan.life(int(k), 1).match: self._oracle()
                        for k in sample}
        for runner in self.runners.values():
            runner.warmup()
        self.tenants: Dict[int, _Tenant] = {}     # every match, by its id
        self.seat: List[Optional[_Tenant]] = [None] * seats
        self.by_handle = {}
        self.seat_at = {}                   # (group, slot) -> seat
        self.due: List[_Tenant] = []        # retired in the last frame
        self.next: Dict[int, _Tenant] = {}  # seat -> the arrival asked for now
        self.ends: Dict[int, List[_Tenant]] = {}
        self.far_ends: Dict[int, FarEnd] = {}
        self.arrivals = self.unread = self.unplanned = 0
        self.left_alone: Optional[_Tenant] = None
        for k in range(seats):
            t = _Tenant(self.plan.life(k, 0), -1)
            self._host(t)
            self._far_end(t)
            t.handle = self.server.add_match(t.host, self._feed(t.life.match))
            self._seated(t)
            self.seat_at[t.handle.group, t.handle.slot] = k
        mark("matches_admitted")
        self.oracle = self._oracle()
        self.oracle.warmup()
        mark("oracle_warm")
        limit = int(ctx.traffic["sync_frames_limit"])
        while not all(self._running(t) for t in self.seat):
            if self.server.frames_served >= limit:
                raise RuntimeError(
                    f"sessions still synchronising after {limit} frames")
            self._serve_one()
        self.scalars["sync_frames"] = self.server.frames_served
        for t in self.seat:
            t.play_from = self.net.now
        for _ in range(int(ctx.traffic["warmup_frames"])):
            self._serve_one()
        # One result read before the window: what it calls is warm.
        self._read_result(self.seat[0])
        self.seat[0].result = None
        self._block()
        mark("sessions_running")

    def _host(self, t: _Tenant) -> None:
        """The server's end of the match; tapped where the player drops."""
        t.host = self._session(0, t.life.match, self.host_metrics)
        if t.life.kind == SILENT_DROP:
            t.tap = t.host.socket = _Tap(t.host.socket, lambda: self.frame)

    def _far_end(self, t: _Tenant) -> None:
        """The remote client of the match, on its own bursty link whose
        clock starts when the match's play does."""
        from bevy_ggrs_tpu.chaos import ChaosSocket

        m = t.life.match
        runner = self.runners.get(m)
        far = self._session(1, m, self.far_metrics if runner else None)
        far.socket = ChaosSocket(
            far.socket, self.bursts.plan(m, self.dt),
            clock=lambda: self.net.now - t.play_from, addr=("ext", m))
        t.far = self.far_ends[m] = FarEnd(far, self._feed(m), runner)

    def _seated(self, t: _Tenant) -> None:
        self.tenants[t.life.match] = self.seat[t.life.seat] = t
        self.by_handle[t.handle] = t

    def _running(self, t: _Tenant) -> bool:
        return (t.host.current_state() == SessionState.RUNNING
                and t.far.session.current_state() == SessionState.RUNNING)

    def _block_far(self) -> None:
        import jax

        jax.block_until_ready([f.runner.state for f in self.far_ends.values()
                               if f.runner is not None])

    def _far_ends(self) -> float:
        t = time.perf_counter()
        with self.ctx.annotate("bench/far_end"):
            for f in list(self.far_ends.values()):
                f.tick()
            self._block_far()
        return time.perf_counter() - t

    # -- the operator ---------------------------------------------------

    def _between(self, frame: int) -> None:
        """Outside the window: the count of what the last frame did, the
        clock, the far ends, and the far end of each arrival due now (it
        ticks from the next frame on, when the server's end exists)."""
        if frame > 0:
            for t in self.seat:
                if t is not None:
                    self._count(t, frame - 1)
        for t in self.ends.get(frame - 1, ()):
            if t.life.kind == SILENT_DROP and t.retired is None:
                self._silence(t)
        self.net.advance(self.dt)
        self._far_ends()
        for old in self.due:
            t = _Tenant(self.plan.life(
                old.life.seat, old.life.generation + 1), frame)
            t.end = frame + t.life.length
            self._far_end(t)
            self.next[old.life.seat] = t
        self.due = []

    def _silence(self, t: _Tenant) -> None:
        t.silent = True
        t.far.session.socket.close()
        del self.far_ends[t.life.match]

    def _count(self, t: _Tenant, frame: int) -> None:
        """The benchmark's own books of one match and one served frame."""
        if t.play_from == NEVER and not t.silent \
                and self._running(t):
            t.play_from = self.net.now  # the link's bursts start with play
        if t.running_at is None:
            if t.host.current_state() != SessionState.RUNNING:
                self.counted["syncing"] += 1
                return
            t.running_at = frame
        now = self._slot(t.handle).frame
        self.counted["attempted"] += 1
        if now > t.frame_seen:
            self.counted["moved"] += 1
            t.advanced += now - t.frame_seen
            t.frame_seen = now
        elif t.host.current_frame - t.host.confirmed_frame() \
                > t.host.max_prediction:
            self.counted["withheld"] += 1

    def _before(self, frame: int) -> None:
        """Every seat freed in the last frame is asked for again."""
        if not self.next:
            return
        from bevy_ggrs_tpu.serve.admission import AdmissionTrace

        for spot in self.server.free_slot_handles():
            t = self.next.pop(self.seat_at[spot.group, spot.slot])
            self._host(t)
            t.handle = self.server.enqueue_match(
                t.host, self._feed(t.life.match),
                trace=AdmissionTrace(t.life.match))
            t.given = self.seat_at[t.handle.group, t.handle.slot]
            self._seated(t)
            self.ends.setdefault(t.end, []).append(t)
            self.arrivals += 1

    def _after(self, frame: int) -> None:
        """What the sessions reported, and the matches that are over."""
        for handle, ev in self.server.drain_events():
            kind = ev.kind
            t = self.by_handle.get(handle)
            if t is None:
                continue
            if kind is EventKind.NETWORK_INTERRUPTED:
                t.interrupted_at = frame
                t.frozen_from = t.host.confirmed_frame()
            elif kind is EventKind.NETWORK_RESUMED:
                t.interrupted_at = t.frozen_from = None
            elif kind is EventKind.DISCONNECTED:
                t.disconnected_at = frame
                if not t.silent:
                    self.unplanned += 1
                elif self.ctx.control == LEAVE_ONE_DROP \
                        and self.left_alone is None:
                    self.left_alone = t
                elif t.retired is None:
                    self._retire(t, frame)
        for t in self.ends.get(frame, ()):
            if t.retired is None and (t.life.kind != SILENT_DROP
                                      or t.running_at is None):
                self._retire(t, frame)

    def _read_result(self, t: _Tenant) -> None:
        host, h = t.host, t.handle
        n = min(host.confirmed_frame() + 1, host.current_frame - 1)
        if n < 0:
            return                      # never played: nothing to record
        ring = self.server.groups[h.group].slot_ring(h.slot)
        row = n % len(ring.frames)
        if int(np.asarray(ring.frames)[row]) != n:
            self.unread += 1
            return
        got_t, got_v, count = self.ctx.title.readback(ring.states,
                                                      self.players)
        t.result = (n, got_t[row], got_v[row], count[row])
        if t.life.match in self.runners:
            import jax

            t.ring = (jax.tree_util.tree_map(np.asarray, ring),
                      host.current_frame)

    def _retire(self, t: _Tenant, frame: int) -> None:
        self._count(t, frame)
        self._read_result(t)
        self.server.retire_match(t.handle)
        t.host.socket.close()
        if not t.silent:
            self._silence(t)
        t.retired = frame
        del self.by_handle[t.handle]
        self.seat[t.life.seat] = None
        self.due.append(t)

    def _serve_one(self) -> None:
        self.net.advance(self.dt)
        self._far_ends()
        self.server.run_frame()

    # -- the measured window --------------------------------------------

    def window(self, seconds: float, pause_at=None, pause=None) -> float:
        """Served frames back to back for ``seconds`` of the server's and
        the operator's own time: what ``_between`` takes, and ``pause()``
        (the traced run stops its profiler there), are taken out."""
        server, annotate = self.server, self.ctx.annotate
        spans, operator, asks, closes = (self.series[name] for name in (
            "serve_frame_ms", "operator_ms", "operator_ask_ms",
            "operator_end_ms"))
        for k, t in enumerate(self.seat):
            t.end = self.plan.first_end(k)
            t.running_at = -1
            t.frame_seen = self._slot(t.handle).frame
            self.ends.setdefault(t.end, []).append(t)
        self.open_counters()
        served0 = server.frames_served
        between_s = 0.0
        skipped = lambda: sum(  # noqa: E731
            t.far.skipped for t in self.tenants.values())
        skipped0 = skipped()
        t0 = started = time.perf_counter()
        while True:
            ts = time.perf_counter()
            if ts - t0 >= seconds:
                break
            if pause is not None and ts - started >= pause_at:
                self._block()
                pause()
                pause = None
                t0 += time.perf_counter() - ts
            tb = time.perf_counter()
            self.frame += 1
            self._between(self.frame)
            spent = time.perf_counter() - tb
            between_s += spent
            t0 += spent
            ts = time.perf_counter()
            with annotate("bench/operator"):
                self._before(self.frame)
            t1 = time.perf_counter()
            with annotate("bench/run_frame"):
                server.run_frame()
            t2 = time.perf_counter()
            with annotate("bench/operator"):
                self._after(self.frame)
            t3 = time.perf_counter()
            spans.append((t2 - t1) * 1e3)
            asks.append((t1 - ts) * 1e3)
            closes.append((t3 - t2) * 1e3)
            operator.append((t1 - ts + t3 - t2) * 1e3)
        with annotate("bench/final_wait"):
            self._block()
        end = time.perf_counter()
        for t in self.seat:
            if t is not None:
                self._count(t, self.frame)
        self.close_counters()
        served = server.frames_served - served0
        self.advanced = sum(t.advanced for t in self.tenants.values())
        self.attempted = self.counted["attempted"]
        self.withheld = self.counted["withheld"]
        self.failed = int(
            self.attempted - self.withheld - self.counted["moved"])
        ended = [t for t in self.tenants.values() if t.retired is not None]
        self.scalars.update({
            "match_frames": int(self.advanced),
            "frames_served": served,
            "frames_withheld": int(self.withheld),
            "slot_frames_syncing": self.counted["syncing"],
            "matches_ended": len(ended),
            "matches_dropped": sum(t.silent and t.disconnected_at is not None
                                   for t in ended),
            "matches_admitted": self.arrivals,
            "far_end_s": between_s,
            "operator_s": sum(operator) / 1e3,
            "operator_ask_s": sum(asks) / 1e3,
            "operator_end_s": sum(closes) / 1e3,
            "far_end_frames_skipped": skipped() - skipped0,
            "live_matches": len(self.seat),
            "virtual_frames": round(self.net.now * self.fps),
        })
        if spans:
            self.scalars["slowest_frame"] = {
                "index": int(np.argmax(spans)), "ms": float(max(spans))}
        return end - t0

    def _counters(self) -> dict:
        sv = self.server
        tot = lambda name: sum(getattr(g, name) for g in sv.groups)  # noqa
        out = dict(
            super(HostedP2PDriver, self)._counters(),
            desync_events=int(
                self.host_metrics.counters.get("desyncs_flagged", 0)
                + self.far_metrics.counters.get("desyncs_flagged", 0)),
            checksum_ballots=int(
                self.far_metrics.counters.get("checksum_ballots", 0)),
            frames_withheld=sv.frames_withheld_total,
            match_frames_attempted=self.counted["attempted"],
            burst_steps_total=tot("burst_steps_total"),
            burst_step_slots_total=tot("burst_step_slots_total"),
            frames_served=sv.frames_served,
            slot_frames_total=sv.frames_served * len(self.seat),
            admissions_completed=sv.admissions_completed,
        )
        for name in ("matches_retired_total", "match_events_delivered_total",
                     "slot_frames_syncing_total", "slot_frames_stalled_total"):
            out[name] = getattr(sv, name)
        return out

    # -- after the window -----------------------------------------------

    def _drain(self) -> None:
        """The seats the last frame freed are asked for, what is in flight
        lands (nobody advances), then one more served frame: after it a
        match's snapshot of frame ``confirmed + 1`` rests on confirmed
        inputs only, and no slot is empty."""
        self.frame += 1
        self._between(self.frame)
        self._before(self.frame)
        playing = [t for t in self.seat if t is not None and not t.silent
                   and t.running_at is not None]
        settled = lambda t: t.host.confirmed_frame() >= min(  # noqa: E731
            t.host.current_frame, t.far.session.current_frame) - 1
        for _ in range(self.notify_frames - 2):
            if all(settled(t) for t in playing):
                break
            self.net.advance(self.dt)
            for core in self.server.groups:
                core.flush_reports()
            for t in playing:
                t.host.poll_remote_clients()
                t.far.poll()
        self.server.run_frame()
        self._block()

    def _rows(self, upto: int) -> list:
        """The ledger the run kept: ``turnover.Turnover.ledger``'s rows."""
        return sorted(
            (t.life.match, getattr(t, "given", t.life.seat), t.first,
             -1 if t.retired is None or t.retired > upto else t.retired)
            for t in self.tenants.values() if t.first <= upto)

    def check(self) -> List[Comparison]:
        sv, last = self.server, self.frame
        # Guarantees (a) and (b) are about the window: read before the drain
        # moves the clock.
        drops = [t for t in self.tenants.values() if t.silent
                 and t.life.kind == SILENT_DROP and t.tap is not None]
        late = 0
        for t in drops:
            heard = t.tap.last_frame
            for at, after in ((t.interrupted_at, self.notify_frames),
                              (t.disconnected_at, self.timeout_frames)):
                due = heard + after
                if at is None:
                    late += last > due + REPORT_SLACK_FRAMES
                else:
                    late += not due <= at <= due + REPORT_SLACK_FRAMES
        waits = {t.life.match: t.disconnected_at - t.end for t in drops
                 if t.disconnected_at is not None}
        planned, kept = self.plan.ledger(waits, last), self._rows(last)
        limit = int(self.ctx.traffic["sync_frames_limit"])
        slow = sum((last if t.running_at is None else t.running_at)
                   - t.first > limit
                   for t in self.tenants.values() if t.first >= 0)
        self.scalars["ledger_rows"] = len(kept)
        self.scalars["disconnect_waits"] = sorted(
            t.disconnected_at - t.tap.last_frame for t in drops
            if t.disconnected_at is not None)
        completed0 = self._base["admissions_completed"]
        self._drain()
        live = [t for t in self.seat if t is not None]
        playing = [t for t in live if t.host.current_frame > 0]
        totals = self._counters()
        depth = sv.groups[0].ring_depth
        upto = {t.life.match: min(t.host.confirmed_frame() + 1,
                                  t.host.current_frame - 1) for t in playing}
        ring_frames = [np.asarray(g.rings.frames) for g in sv.groups]
        left = sum(int(ring_frames[t.handle.group][
            t.handle.slot, upto[t.life.match] % depth] != upto[t.life.match])
            for t in playing)
        out = [
            Comparison("guarantee.desync_events", totals["desync_events"], 0),
            Comparison("guarantee.no_ballot_compared",
                       float(self._delta["checksum_ballots"] <= 0), 0),
            Comparison("guarantee.match_frames_failed", float(self.failed), 0),
            Comparison("guarantee.withheld_frames_miscounted",
                       abs(self._delta["frames_withheld"] - self.withheld), 0),
            Comparison("guarantee.slot_faults", sv.faults_total, 0),
            Comparison("guarantee.quarantined",
                       sv.slots_quarantined + sv.slots_recovering, 0),
            Comparison("guarantee.evictions", sv.evictions_total, 0),
            Comparison("guarantee.unplanned_disconnects", self.unplanned, 0),
            Comparison("guarantee.drop_reports_out_of_bounds", int(late), 0),
            Comparison("guarantee.ledger_rows_differ_from_plan",
                       len(set(planned) ^ set(kept)), 0),
            Comparison("guarantee.arrivals_not_running_in_time",
                       int(slow), 0),
            Comparison("guarantee.slots_not_refilled",
                       len(self.seat) - sv.slots_active, 0),
            Comparison("guarantee.admissions_incomplete", abs(
                self.arrivals - (sv.admissions_completed - completed0)
                - sum(t.host.current_state() != SessionState.RUNNING
                      for t in live)), 0),
            Comparison("guarantee.results_unread", self.unread, 0),
            Comparison("guarantee.confirmed_frame_left_ring", left, 0),
        ]
        if left:
            return out
        out += self._successors()

        # (c) every result read at a retirement and every match live at
        # the end, against the plain reference replaying the match's own
        # generated inputs from its own spawn; a dropped player's inputs
        # repeat the last one the server had confirmed.
        got = [self.ctx.title.readback(g.rings.states, self.players)
               for g in sv.groups]
        cases = [(t, *t.result) for t in self.tenants.values()
                 if t.result is not None]
        for t in playing:
            n, h = upto[t.life.match], t.handle
            cases.append((t, n) + tuple(
                got[h.group][j][h.slot, n % depth] for j in range(3)))
        frames = np.asarray([c[1] for c in cases])
        bits = np.stack([self._inputs(c[0], int(frames.max()))
                         for c in cases])
        want_t, want_v, want_frames = self.ctx.reference.replay(bits, frames)
        limits = limits_of(self.ctx.config)
        out.append(Comparison(
            "reference.frame_count_gap", float(np.abs(
                np.asarray([c[4] for c in cases]).astype(np.int64)
                - want_frames.astype(np.int64)).max()), 0))
        out += [Comparison(name, gap, limits[name]) for name, gap in
                reference_gaps(np.stack([c[2] for c in cases]),
                               np.stack([c[3] for c in cases]),
                               want_t, want_v)]
        self.scalars["checked_matches"] = len(cases)
        self.scalars["checked_results"] = len(cases) - len(playing)
        self.scalars["checked_frames_each"] = [int(frames.min()),
                                               int(frames.max())]
        return out

    def _inputs(self, t: _Tenant, frames: int) -> np.ndarray:
        """``uint8[P, frames]``: what the match's players pressed, as the
        server's session came to know it. A player who dropped is fed the
        last input the server had confirmed from the frame after it on."""
        bits = self.keys.table(frames)[t.life.match][:, :frames].copy()
        if t.frozen_from is not None:
            bits[1, t.frozen_from + 1:] = (
                bits[1, t.frozen_from] if t.frozen_from >= 0 else 0)
        return bits

    def _successors(self) -> List[Comparison]:
        """(d) the sampled seats' second tenants, bitwise: every row of
        the ring that the tenant's confirmed frames wrote is a serial
        ``RollbackRunner`` replay of its inputs (``_inputs``) from ITS frame 0,
        and every other row is empty or one of its own predicted frames:
        nothing of the predecessor is left."""
        from bevy_ggrs_tpu.schedule import CONFIRMED
        from bevy_ggrs_tpu.session.requests import AdvanceFrame, SaveGameState
        from bevy_ggrs_tpu.state import ring_load

        status = np.full((self.players,), CONFIRMED, np.int32)
        burst = int(self.ctx.config["settings"]["max_prediction"])
        differ = compared = 0
        for m in self.runners:
            t = self.tenants.get(m)
            if t is None or t.handle is None:
                continue
            if t.ring is not None:
                ring, current = t.ring
                n = t.result[0]
            elif t.retired is None and t.host.current_frame > 0:
                h = t.handle
                ring = self.server.groups[h.group].slot_ring(h.slot)
                current = t.host.current_frame
                n = min(t.host.confirmed_frame() + 1, current - 1)
            else:
                continue
            compared += 1
            labels = np.asarray(ring.frames)
            bits = self._inputs(t, n + 1)
            oracle, same = self.oracle, True
            oracle.restore_state(0, self.initial)
            step = lambda f: [SaveGameState(f), AdvanceFrame(  # noqa: E731
                bits=np.ascontiguousarray(bits[:, f]), status=status)]
            # In bursts up to where the ring's rows begin, then a frame a
            # call, each row against the state that entered its frame.
            rows_from = max(0, n - len(labels) + 1)
            for f0 in range(0, rows_from, burst):
                oracle.handle_requests(sum(
                    (step(f) for f in range(f0, min(f0 + burst, rows_from))),
                    []))
            for f in range(rows_from, n + 1):
                if labels[f % len(labels)] == f:
                    same &= tree_equal(ring_load(ring, f), oracle.state)
                if f < n:
                    oracle.handle_requests(step(f))
            same &= labels[n % len(labels)] == n
            same &= all(f == -1 or n - len(labels) < f <= current
                        for f in labels.tolist())
            differ += not same
        self.scalars["successors_compared"] = compared
        return [
            Comparison("guarantee.sampled_successors_differ_from_serial_replay",
                       differ, 0),
            Comparison("guarantee.no_successor_compared",
                       float(compared == 0), 0),
        ]

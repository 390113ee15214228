"""Loop kind ``match_server_world``: ``match_server`` for a title whose step
couples a whole world of entities (boids), sized by the configuration.

The loop, the occupancy, the timing and every ``guarantee.*`` comparison
are ``match_server``'s, inherited: one ``MatchServer``, hosted SyncTest
matches, ``run_frame()`` back to back, sampled slots bitwise the serial
``RollbackRunner``. Three things differ:

- the title is bound to the configuration's ``settings`` (the size of the
  world, the force path), which the accepted hooks ``make_schedule(control)``
  and ``make_world(players)`` cannot carry (``_Sized`` below; what
  ``p2p_pair_world`` does for the client through ``title.configured``);
- the reference half of ``check()``. A coupled step with radius tests is
  chaotic (``p2p_pair_world``'s docstring), so a replay from spawn compares
  nothing: the reference is **anchored on what the timed path produced**.
  Every group's ring still holds its matches' last confirmed frames (a
  SyncTest match confirms every frame it steps). For EVERY live match the
  newest step ``f -> f + 1`` whose two ends its ring holds, and for the
  sampled slots every held step, the plain reference steps the program's
  own state at ``f`` through the generator's input of ``f`` and is compared
  with the program's state at ``f + 1``: all boids, positions on the torus,
  each step from the program's exact bits. A boid is left out of a step's
  float comparison only if the reference cannot decide its step: its own
  float64 distances put one of its pairs within ``undecided_margin`` of a
  radius, or its velocity before the speed clamp is shorter than
  ``MIN_SPEED / undecided_clamp_gain``, so that the clamp stretches it,
  and whatever rounding its force carries, by more than that factor (the
  rules normalise a near-zero vector to the minimum speed: a boid whose
  force cancelled its velocity to 2e-5 had a 1e-7 rounding stretched 918
  times, the one ``correct: false`` of PR 37's first 16 sound runs; a
  run here compares 290,000 boid-steps where the client's compares 8,000,
  so it meets such a boid about once a run). The share left out is itself
  compared. The steps are independent, so they run on a thread pool (NumPy
  releases the interpreter lock): 256 + ~28 steps of 1,024 boids in ~4 s of
  host time on the chip's machine, outside the window;
- ``cost_shapes()`` adds what ``benchmark/costs/pairwise_force_served.py``
  counts from; ``counters()`` adds the burst's exact step counts
  (``burst_fill_share.serve``), and a traced run's scalars carry the
  program's one-sample series ``serve_carry_bytes`` (observed at warm-up,
  before the window the series readers look at).
"""

from __future__ import annotations

import dataclasses
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List

import numpy as np

from benchmark.drivers.common import Comparison, Context, limits_of
from benchmark.drivers.match_server import Driver as MatchServerDriver
from benchmark.drivers.p2p_pair_world import _NoReplay


class _Sized:
    """A title module with ``make_schedule`` / ``make_world`` bound to a
    configuration's ``settings``: what ``match_server`` asks of
    ``ctx.title``. ``title.configured`` reads the settings (and refuses, by
    name, a program whose force path cannot run them)."""

    def __init__(self, title, settings: dict):
        self._title = title
        self._sized = title.configured(settings)
        self.REFERENCE = title.REFERENCE
        self.CONTROLS = title.CONTROLS
        self.input_spec = title.input_spec
        self.readback = title.readback

    def make_schedule(self, control=None):
        return self._title.make_schedule(control, self._sized.force_kernel)

    def make_world(self, num_players: int):
        return self._title.make_world(num_players, self._sized.num_entities)


def _speed_before_clamp(ref, position, velocity, bits) -> np.ndarray:
    """float64[N]: the length of ``velocity + force`` of one match's boids,
    leaders' steering included, before the reference's ``step`` clamps it
    into ``MIN_SPEED .. MAX_SPEED`` (which ``step`` does not hand out: the
    force is evaluated a second time here, and the steering rule restated)."""
    nv = (velocity + ref.forces(position[None], velocity[None])[0]).astype(
        np.float64)
    inp = np.asarray(bits).astype(np.uint32)
    held = lambda mask: ((inp & mask) != 0).astype(np.float64)  # noqa: E731
    steer = float(ref.LEADER_STEER)
    nv[:inp.size, 0] += (held(ref.INPUT_RIGHT) - held(ref.INPUT_LEFT)) * steer
    nv[:inp.size, 1] += (held(ref.INPUT_DOWN) - held(ref.INPUT_UP)) * steer
    return np.sqrt((nv * nv).sum(axis=-1))


class Driver(MatchServerDriver):
    def __init__(self, ctx: Context):
        self.plain_reference = ctx.reference
        super().__init__(dataclasses.replace(
            ctx, title=_Sized(ctx.title, ctx.config["settings"]),
            reference=_NoReplay))
        self.margin = float(ctx.config["undecided_margin"])
        self.clamp_gain = float(ctx.config["undecided_clamp_gain"])

    def setup(self, mark=lambda name: None) -> None:
        super().setup(mark)
        if self.program_metrics is not None:
            carried = self.program_metrics.series.get("serve_carry_bytes")
            if carried:     # a program older than PR 37 observes none
                self.scalars["serve_carry_bytes"] = float(carried[-1])
            # Which form the program's bursts carry their ring rows in.
            self.scalars["ring_row_lowering"] = {
                k: v for k, v in self.program_metrics.counters.items()
                if k.startswith("ring_row_lowering")}

    def _counters(self) -> dict:
        out = super()._counters()
        for name in ("burst_steps_total", "burst_step_slots_total"):
            out[name] = sum(getattr(g, name) for g in self.server.groups)
        return out

    # -- after the window -----------------------------------------------

    def check(self) -> List[Comparison]:
        out = [c for c in super().check()
               if not c.name.startswith("reference.")]
        t = time.perf_counter()
        out += self._anchored()
        self.scalars["reference_s"] = time.perf_counter() - t
        self.scalars["slo_deadline_misses"] = self._deadline_misses()
        return out

    def _deadline_misses(self) -> int:
        """Match-ticks whose host time passed the watchdog's budget, as the
        server's SLO sampled them (its long window: the last 512 ticks a
        slot). A miss is a strike; ``strike_limit`` in a row fault the slot,
        which ``guarantee.slot_faults`` holds at 0."""
        s = self.ctx.config["settings"]
        per_group = int(s["capacity"]) // int(s["stagger_groups"])
        missed = 0.0
        for h in self.live.values():
            rates = self.server.slo.burn_rates(h.group * per_group + h.slot)
            d = rates.get("deadline", {})
            missed += d.get("long_bad", 0.0) * d.get("long_n", 0)
        return int(round(missed))

    def _anchored(self) -> List[Comparison]:
        """The reference's rows: per live match the newest held step, per
        sampled slot every held step, each from the program's own state."""
        ref, title = self.plain_reference, self.ctx.title
        horizon = int(self._frames().max()) + 1
        table = self.keys.table(horizon)                   # [M, P, F]
        sampled = set(self.sample)
        jobs = []       # (match, f, pos f, vel f, pos f+1, vel f+1, counts)
        for g, core in enumerate(self.server.groups):
            rings = core.rings
            frames = np.asarray(rings.frames)              # [S, depth]
            pos, vel, count = title.readback(rings.states, self.players)
            for k, h in self.live.items():
                if h.group != g:
                    continue
                held = {int(f): row
                        for row, f in enumerate(frames[h.slot]) if f >= 0}
                steps = sorted(f for f in held if f + 1 in held)
                for f in (steps if k in sampled else steps[-1:]):
                    a, b = held[f], held[f + 1]
                    jobs.append((
                        k, f, pos[h.slot, a], vel[h.slot, a],
                        pos[h.slot, b], vel[h.slot, b],
                        abs(int(count[h.slot, a]) - f)
                        + abs(int(count[h.slot, b]) - (f + 1))))
        covered = {k for k, *_ in jobs}
        self.scalars["anchored_steps"] = len(jobs)
        self.scalars["anchored_matches"] = len(covered)
        if len(covered) < len(self.live):
            return [Comparison("reference.no_step_held",
                               float(len(self.live) - len(covered)), 0)]

        def one(job):
            k, f, p0, v0, p1, v1, _ = job
            want_p, want_v = ref.step(p0[None], v0[None],
                                      table[k][None, :, f])
            decided = ~(ref.undecided(p0, self.margin)
                        | (_speed_before_clamp(ref, p0, v0, table[k][:, f])
                           < float(ref.MIN_SPEED) / self.clamp_gain))
            d_t = ref.torus_gap(p1, want_p[0]).max(axis=-1)
            d_v = np.abs(v1.astype(np.float64) - want_v[0]).max(axis=-1)
            return (np.where(decided, d_t, 0.0), np.where(decided, d_v, 0.0),
                    int((~decided).sum()))

        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            done = list(pool.map(one, jobs))
        gap_t = np.stack([d[0] for d in done])             # [jobs, N]
        gap_v = np.stack([d[1] for d in done])
        worst = int(gap_t.max(axis=1).argmax())
        self.scalars["anchored_worst"] = {
            "match": int(jobs[worst][0]), "frame": int(jobs[worst][1]),
            "boid": int(gap_t[worst].argmax())}
        limits = limits_of(self.ctx.config)
        return [
            Comparison("reference.frame_count_gap",
                       float(max(j[6] for j in jobs)), 0),
            Comparison("reference.translation_gap", float(gap_t.max()),
                       limits["reference.translation_gap"]),
            Comparison("reference.velocity_gap", float(gap_v.max()),
                       limits["reference.velocity_gap"]),
            Comparison("reference.undecided_share",
                       sum(d[2] for d in done) / gap_t.size,
                       limits["reference.undecided_share"]),
        ]

    def cost_shapes(self) -> dict:
        """``match_server``'s byte counts, and what one group's dispatch
        steps, for benchmark/costs/pairwise_force_served.py."""
        s = self.ctx.config["settings"]
        out = super().cost_shapes()
        out.update({
            "num_slots": int(s["capacity"]) // int(s["stagger_groups"]),
            "num_entities": int(s["num_entities"]),
            "speculation_branches": int(s["speculation_branches"]),
            "speculation_frames": int(s["speculation_frames"]),
            "live_frames": int(s["check_distance"]) + 1,
        })
        return out

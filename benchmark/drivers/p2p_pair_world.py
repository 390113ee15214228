"""Loop kind ``p2p_pair_world``: ``p2p_pair`` for a title whose step couples
a whole world of entities (boids), sized by the configuration.

The loop, the traffic, the timing and every ``guarantee.*`` comparison are
``p2p_pair``'s, inherited. Two things differ:

- the title is handed the configuration's ``settings`` (``title.configured``:
  the size of the world, the force path), which ``build_plugin(num_players,
  control)`` cannot carry;
- the reference half of ``check()``. A coupled step with radius tests is
  chaotic: one rounding of a position flips a test, after which two float32
  machines part for good, so a replay of some 1,800 frames from spawn compares
  nothing. The reference is **anchored on what the timed path produced**:
  peer 0's ring still holds the confirmed frames ``a .. u`` (``u`` =
  confirmed + 1, ``a`` the oldest held: up to the window's 8 steps back).
  For every ``f`` in ``a .. u-1`` the plain reference steps the program's
  own state at ``f`` through the confirmed input of ``f``, and its positions
  and velocities are compared with the program's state at ``f + 1``: all
  boids, every held step, each step from the program's exact bits. (One
  chain of 8 reference steps from ``a`` would not do: a boid whose test
  flipped is displaced by up to a frame's travel, its neighbours feel that
  on the next step, and two steps later the whole flock is "undecided".)
  A boid is left out of a step's float comparison only if the reference's
  own float64 distances put one of its pairs within ``undecided_margin`` of
  a radius on that step (``reference.undecided``); the share left out is
  itself compared with a limit, so an answer the reference cannot decide is
  counted, not hidden.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from benchmark.drivers.common import Comparison, Context, limits_of
from benchmark.drivers.p2p_pair import Driver as P2PPairDriver


class _NoReplay:
    """Stands where ``p2p_pair.check()`` looks for its replay from spawn and
    costs nothing: the ``reference.*`` rows made from its zeros mean nothing,
    and ``check()`` below drops them for the anchored ones."""

    @staticmethod
    def replay(bits, frames):
        del bits, frames
        return np.zeros((1, 1)), np.zeros((1, 1)), np.zeros((1,), np.uint32)


class Driver(P2PPairDriver):
    def __init__(self, ctx: Context):
        self.plain_reference = ctx.reference
        super().__init__(dataclasses.replace(
            ctx, title=ctx.title.configured(ctx.config["settings"]),
            reference=_NoReplay))
        self.margin = float(ctx.config["undecided_margin"])

    def check(self) -> List[Comparison]:
        out = [c for c in super().check()
               if not c.name.startswith("reference.")]
        if "checked_frame" in self.scalars:     # frame u was still held
            out += self._anchored(int(self.scalars["checked_frame"]))
        return out

    def _anchored(self, upto: int) -> List[Comparison]:
        """The reference's rows: every held confirmed step ``f -> f + 1``
        with ``f + 1 <= upto``, each from the program's own state at ``f``."""
        ref, title = self.plain_reference, self.ctx.title
        ring = self.runner.ring
        held = {int(f): slot for slot, f in enumerate(np.asarray(ring.frames))}
        pos, vel, count = title.readback(ring.states, self.players)
        table = self.keys.table(upto)[0]                     # [P, F]
        steps = [f for f in range(upto - self.window_frames, upto)
                 if f >= 0 and f in held and f + 1 in held]
        gap_t = gap_v = gap_n = 0.0
        left_out = 0
        worst = None
        for f in steps:
            s0, s1 = held[f], held[f + 1]
            want_p, want_v = ref.step(pos[s0][None], vel[s0][None],
                                      table[None, :, f])
            decided = ~ref.undecided(pos[s0], self.margin)
            left_out += int((~decided).sum())
            gap_n = max(gap_n, abs(int(count[s1]) - (f + 1)),
                        abs(int(count[s0]) - f))
            d_t = ref.torus_gap(pos[s1], want_p[0]).max(axis=-1)
            d_v = np.abs(vel[s1].astype(np.float64) - want_v[0]).max(axis=-1)
            d_t, d_v = np.where(decided, d_t, 0.0), np.where(decided, d_v, 0.0)
            if d_t.max() >= gap_t:
                worst = {"frame": f, "boid": int(d_t.argmax())}
            gap_t = max(gap_t, float(d_t.max()))
            gap_v = max(gap_v, float(d_v.max()))
        boids = pos.shape[-2]
        limits = limits_of(self.ctx.config)
        self.scalars["anchored_steps"] = len(steps)
        self.scalars["anchored_worst"] = worst
        if not steps:
            return [Comparison("reference.no_step_held", 1.0, 0)]
        return [
            Comparison("reference.frame_count_gap", float(gap_n), 0),
            Comparison("reference.translation_gap", gap_t,
                       limits["reference.translation_gap"]),
            Comparison("reference.velocity_gap", gap_v,
                       limits["reference.velocity_gap"]),
            Comparison("reference.undecided_share",
                       left_out / (len(steps) * boids),
                       limits["reference.undecided_share"]),
        ]

    def cost_shapes(self) -> dict:
        """For benchmark/costs/pairwise_force.py: what one tick steps."""
        s = self.ctx.config["settings"]
        return {
            "num_entities": int(s["num_entities"]),
            "speculation_branches": int(s["speculation_branches"]),
            "speculation_frames": int(s["speculation_frames"]),
            "live_frames": 1,
        }

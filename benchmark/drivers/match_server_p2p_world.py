"""Loop kind ``match_server_p2p_world``: ``match_server_p2p`` for a title
whose step couples a whole world of entities (boids), sized by the
configuration, in a loop where the far ends' time cannot hide the device.

One ``MatchServer`` whose every match is a hosted P2P session behind the
mix's network: the set-up, the far ends, the withheld count, the drain and
every ``guarantee.*`` row are ``match_server_p2p``'s, inherited. What
differs:

- the title is bound to the configuration's ``settings`` (the size of the
  world, the force path) as ``match_server_world`` binds it (``_Sized``);
- **the window loses no device time.** ``match_server_p2p`` stops its clock
  for the far ends with four dispatches in flight, so a device program of up
  to a far-end stretch costs its cell nothing. Here a served frame ends when
  the device has finished it (``_frame_to_its_end``: ``run_frame()``, then
  ``_block()``, both inside the window and inside ``serve_frame_ms``); only
  then does the clock stop and the far ends tick, and the next frame starts
  on an idle device. ``guarantee.device_busy_outside_window`` counts the
  stops of the clock that no completed ``_block()`` preceded. The price
  against a deployment, whose far ends are other machines and whose device
  never drains: the first group's host tick of a frame is no longer hidden
  behind the last frame's dispatches (the configuration's
  ``reduced.device_drained_each_frame``), and the program's own wait
  (``checksum_sync``) finds its reports ready: the device's time is the
  series ``device_drain_ms`` of this loop instead;
- the reference half of ``check()`` is ``match_server_world``'s anchored
  one (its ``_anchored``, called and not copied) **on confirmed frames
  only**: it is shown every group's ring with the rows past
  ``min(confirmed + 1, current - 1)`` marked empty, so a step that rests on
  a predicted input is never taken. The replay from spawn is dropped
  (a chaotic step: ``p2p_pair_world``'s docstring);
- ``guarantee.no_speculation_hit``: since play began (warm-up included) at
  least one rollback was recovered in full from a slot's rollout and at
  least one frame absorbed, so the cell cannot be won by never committing;
- ``counters()`` adds the absorb phase's exact pair
  (``absorb_fill_share.serve``); ``cost_shapes()`` the world's sizes.
"""

from __future__ import annotations

import copy
import dataclasses
import time
import types
from typing import Dict, List

import numpy as np

from benchmark.drivers.common import Comparison, Context
from benchmark.drivers.match_server_p2p import Driver as MatchServerP2PDriver
from benchmark.drivers.match_server_world import Driver as WorldDriver
from benchmark.drivers.match_server_world import _Sized
from benchmark.drivers.p2p_pair_world import _NoReplay


class _DrainedFrames:
    """The server as the inherited window loop sees it: ``run_frame()`` is
    ``frame(server)``, the driver's served frame that ends when the device
    has finished it. Everything else is the server's."""

    def __init__(self, server, frame):
        self._server, self._frame = server, frame

    def __getattr__(self, name):
        return getattr(self._server, name)

    def run_frame(self) -> None:
        self._frame(self._server)


class Driver(MatchServerP2PDriver):
    def __init__(self, ctx: Context):
        self.plain_reference = ctx.reference
        super().__init__(dataclasses.replace(
            ctx, title=_Sized(ctx.title, ctx.config["settings"]),
            reference=_NoReplay))
        self.series["device_drain_ms"] = []
        self.margin = float(ctx.config["undecided_margin"])
        self.clamp_gain = float(ctx.config["undecided_clamp_gain"])
        self.drained = True     # no dispatch in flight (set-up ends in a wait)
        self.busy_stops = 0     # stops of the clock over a working device

    def setup(self, mark=lambda name: None) -> None:
        super().setup(mark)
        if self.program_metrics is not None:
            carried = self.program_metrics.series.get("serve_carry_bytes")
            if carried:
                self.scalars["serve_carry_bytes"] = float(carried[-1])

    # -- the measured window --------------------------------------------

    def _block(self) -> None:
        super()._block()
        self.drained = True

    def _frame_to_its_end(self, server) -> None:
        """A served frame and the part of it the program does not wait
        for, the device's: inside the window, and timed."""
        self.drained = False
        server.run_frame()
        t = time.perf_counter()
        with self.ctx.annotate("bench/device_drain"):
            self._block()
        self.series["device_drain_ms"].append(
            (time.perf_counter() - t) * 1e3)

    def _far_ends(self) -> float:
        # The window's clock stands while the far ends tick.
        self.busy_stops += not self.drained
        return super()._far_ends()

    def window(self, seconds: float, pause_at=None, pause=None) -> float:
        self.busy_stops = 0
        served = self.server
        self.server = _DrainedFrames(served, self._frame_to_its_end)
        try:
            window_s = super().window(seconds, pause_at, pause)
        finally:
            self.server = served
        # Hits and absorbed frames since play began: before it no match
        # advanced, so the totals are play's.
        self.scalars["spec_hits_since_play"] = self._total("spec_hits")
        self.scalars["absorbed_frames_since_play"] = self._total(
            "absorb_steps_total")
        return window_s

    def _total(self, name: str) -> int:
        return int(sum(getattr(g, name) for g in self.server.groups))

    def _counters(self) -> dict:
        out = super()._counters()
        for name in ("absorb_steps_total", "absorb_step_slots_total"):
            out[name] = self._total(name)
        return out

    # -- after the window -----------------------------------------------

    def check(self) -> List[Comparison]:
        out = [c for c in super().check()
               if not c.name.startswith("reference.")]
        held = out[-1].name.startswith("guarantee.sampled_matches")
        out += [
            Comparison("guarantee.no_speculation_hit", float(
                self.scalars["spec_hits_since_play"] <= 0
                or self.scalars["absorbed_frames_since_play"] <= 0), 0),
            Comparison("guarantee.device_busy_outside_window",
                       self.busy_stops, 0),
        ]
        if held:    # else a confirmed frame left its ring: nothing to step
            t = time.perf_counter()
            out += WorldDriver._anchored(self._confirmed_view())
            self.scalars["reference_s"] = time.perf_counter() - t
        # Not compared, as in ``match_server_world``: match-ticks whose HOST
        # time passed the watchdog's budget (strike_limit in a row would
        # fault the slot, which ``guarantee.slot_faults`` holds at 0).
        self.scalars["slo_deadline_misses"] = WorldDriver._deadline_misses(
            self)
        return out

    def _confirmed_upto(self) -> Dict[int, int]:
        """Per live match the newest frame whose snapshot rests on
        confirmed inputs only (``match_server_p2p.check``'s ``upto``)."""
        return {k: min(self.hosts[k].confirmed_frame() + 1,
                       self.hosts[k].current_frame - 1) for k in self.live}

    def _confirmed_view(self):
        """This driver as ``match_server_world._anchored`` reads one, its
        groups' rings with every row past a match's confirmed frame marked
        empty: the steps it then takes end at ``confirmed + 1`` at most."""
        upto = self._confirmed_upto()
        groups = []
        for g, core in enumerate(self.server.groups):
            rings = core.rings
            frames = np.array(rings.frames)                 # [S, depth]
            for k, h in self.live.items():
                if h.group == g:
                    row = frames[h.slot]
                    row[row > upto[k]] = -1
            groups.append(types.SimpleNamespace(
                slots=core.slots, rings=types.SimpleNamespace(
                    frames=frames, states=rings.states)))
        view = copy.copy(self)
        view.server = types.SimpleNamespace(groups=groups)
        return view

    def cost_shapes(self) -> dict:
        """``match_server_p2p``'s byte counts (what
        benchmark/costs/batched_tick.py's floor counts from) and the sizes of
        one group's dispatch. Its live frames are no shape here: a function
        that counts this cell's force evaluations would take them from the
        burst's counters."""
        s = self.ctx.config["settings"]
        out = super().cost_shapes()
        out.update({
            "num_slots": int(s["capacity"]) // int(s["stagger_groups"]),
            "num_entities": int(s["num_entities"]),
            "speculation_branches": int(s["speculation_branches"]),
            "speculation_frames": int(s["speculation_frames"]),
        })
        return out

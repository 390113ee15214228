"""Loop kind ``match_server_p2p_churn``: ``match_server_p2p_world`` for a
title whose entities are born and die inside the step (particles), seeded a
match.

One ``MatchServer`` whose every match is a hosted P2P session behind the
mix's network. The loop (a served frame ends when the device has finished
it; the far ends tick outside the window), the set-up, the withheld count,
the drain and every ``guarantee.*`` row are ``match_server_p2p_world``'s,
inherited; the world a match and the comparison by rollback id are
``match_server_churn``'s, its functions as they stand. What differs from
both:

- **every match is admitted with a seed of its own** into the inherited
  set-up, which admits with the server's template: from the moment the
  server is warm, ``add_match`` goes through ``_OwnWorlds`` and hands the
  match its spawn world (``initial_state=``), and the serial runner of a
  sampled far end starts from the same. The seeds are drawn from ``--seed``;
- the serial replay of the sampled matches (``match_server_p2p.check``)
  restores its oracle to "the spawn world": ``_FromItsOwnWorld`` makes that
  the sampled match's own;
- the reference half of ``check()`` is ``match_server_churn._by_id`` **on
  confirmed frames only**: it is shown, in place of every group's live
  states, the ring rows of frame ``min(confirmed + 1, current - 1)`` of each
  match (``_confirmed_rows``), and replays the generator's inputs from
  admission to that frame: a particle born under a mispredicted remote input
  is never compared where the misprediction put it. A match whose confirmed
  frame left its ring fails the run by
  ``guarantee.confirmed_frame_left_ring`` and nothing is replayed;
- ``cost_shapes()`` is ``match_server_p2p``'s (byte counts for
  ``benchmark/costs/batched_tick.py``): the world's sizes are no shape of a
  kernel here.
"""

from __future__ import annotations

import copy
import dataclasses
import time
import types
from typing import List

import numpy as np

from benchmark.drivers.common import Comparison, Context
from benchmark.drivers.match_server_churn import Driver as ChurnDriver
from benchmark.drivers.match_server_p2p import Driver as MatchServerP2PDriver
from benchmark.drivers.match_server_p2p_world import Driver as P2PWorldDriver
from benchmark.drivers.match_server_world import Driver as WorldDriver
from benchmark.drivers.p2p_pair_world import _NoReplay


class _OwnWorlds:
    """The server as the inherited set-up sees it while it admits:
    ``add_match(session, local_inputs)`` admits the match with
    ``world()``, the spawn world of its own seed."""

    def __init__(self, server, world):
        self.served, self._world = server, world

    def __getattr__(self, name):
        return getattr(self.served, name)

    def add_match(self, session, local_inputs):
        return self.served.add_match(session, local_inputs,
                                     initial_state=self._world())


class _FromItsOwnWorld:
    """The serial oracle as the inherited ``check()`` uses it: restored to
    "the spawn world" once a sampled match, in ``sample``'s order, it takes
    that match's own."""

    def __init__(self, runner, worlds):
        self._runner, self._worlds = runner, worlds

    def __getattr__(self, name):
        return getattr(self._runner, name)

    def restore_state(self, frame: int, template) -> None:
        del template
        self._runner.restore_state(frame, next(self._worlds))


class Driver(P2PWorldDriver):
    # ``match_server_churn``'s world a match and its comparison by id.
    _world_of = ChurnDriver._world_of
    _replayed = ChurnDriver._replayed
    _by_id = ChurnDriver._by_id
    _lowering_scalars = ChurnDriver._lowering_scalars

    def __init__(self, ctx: Context):
        # ``match_server_p2p_world``'s own, less what binds a boids title.
        self.plain_reference = ctx.reference
        MatchServerP2PDriver.__init__(self, dataclasses.replace(
            ctx, title=ctx.title.configured(ctx.config["settings"]),
            reference=_NoReplay))
        self.series["device_drain_ms"] = []
        self.drained = True
        self.busy_stops = 0
        rng = np.random.Generator(np.random.PCG64([ctx.seed, 0x5EED]))
        self.seeds = rng.integers(
            0, 2 ** 32, size=int(ctx.traffic["occupancy"]["admit"]),
            dtype=np.uint32)
        self._setting_up = 0    # the match the inherited set-up is building

    # -- set-up ---------------------------------------------------------

    def _session(self, me: int, k: int, metrics=None):
        self._setting_up = k
        return super()._session(me, k, metrics)

    def _oracle(self):
        """A fresh serial singleton from the spawn world of the match being
        set up (a sampled far end's runner; the check's own is restored a
        sampled match)."""
        from bevy_ggrs_tpu.runner import RollbackRunner

        s = self.ctx.config["settings"]
        return RollbackRunner(
            self.schedule, self._world_of(self._setting_up),
            int(s["max_prediction"]), self.players,
            self.ctx.title.input_spec())

    def setup(self, mark=lambda name: None) -> None:
        def marked(name):
            if name == "server_warm":   # built, warm, nobody admitted yet
                self.server = _OwnWorlds(
                    self.server, lambda: self._world_of(self._setting_up))
            mark(name)

        super().setup(marked)
        self.server, self.serial = self.server.served, self.oracle
        if self.program_metrics is not None:
            self._lowering_scalars()

    # -- after the window -----------------------------------------------

    def check(self) -> List[Comparison]:
        self.oracle = _FromItsOwnWorld(
            self.serial, (self._world_of(k) for k in self.sample))
        out = [c for c in MatchServerP2PDriver.check(self)
               if not c.name.startswith("reference.")]
        held = out[-1].name.startswith("guarantee.sampled_matches")
        out += [
            Comparison("guarantee.no_speculation_hit", float(
                self.scalars["spec_hits_since_play"] <= 0
                or self.scalars["absorbed_frames_since_play"] <= 0), 0),
            Comparison("guarantee.device_busy_outside_window",
                       self.busy_stops, 0),
        ]
        if held:    # else a confirmed frame left its ring: nothing to read
            t = time.perf_counter()
            out += self._confirmed_rows()._by_id()
            self.scalars["reference_s"] = time.perf_counter() - t
        self.scalars["slo_deadline_misses"] = WorldDriver._deadline_misses(
            self)
        return out

    def _confirmed_rows(self):
        """This driver as ``match_server_churn._by_id`` reads one: every
        group's "live states" are its ring's rows of the newest frame that
        rests on confirmed inputs only, a match a row, and a match's frame
        is that frame."""
        import jax

        upto = self._confirmed_upto()
        groups = []
        for g, core in enumerate(self.server.groups):
            rings = core.rings
            depth = rings.frames.shape[1]
            row = np.zeros((len(core.slots),), np.int64)
            for k, h in self.live.items():
                if h.group == g:
                    row[h.slot] = upto[k] % depth
            lanes = np.arange(row.size)
            groups.append(types.SimpleNamespace(
                states=jax.tree_util.tree_map(
                    lambda x: np.asarray(x)[lanes, row], rings.states)))
        view = copy.copy(self)
        view.server = types.SimpleNamespace(groups=groups)
        view._frames = lambda: np.asarray([upto[k] for k in self.live])
        return view

    def cost_shapes(self) -> dict:
        return MatchServerP2PDriver.cost_shapes(self)

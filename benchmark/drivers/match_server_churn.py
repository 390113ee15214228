"""Loop kind ``match_server_churn``: ``match_server`` for a title whose
entities are born and die inside the step (particles), sized by the
configuration and seeded a match.

The loop, the timing and every ``guarantee.*`` comparison are
``match_server``'s, inherited: one ``MatchServer``, hosted SyncTest matches,
``run_frame()`` back to back, sampled slots bitwise the serial
``RollbackRunner`` (state, frame, ring frames, ring checksums; ``alive`` and
``rollback_id`` are leaves of the state). A loop kind of its own because:

- the title is bound to the configuration's ``settings`` (births a frame,
  rows of a world), which the accepted hooks cannot carry, and **every
  match is admitted with a seed of its own** (``initial_state=``: the spawn
  world with its ``match_seed``, drawn from ``--seed``), so the 256 worlds
  turn over differently; the serial oracle of a sampled slot starts from
  that match's world;
- the comparison differs. Rows have no identity here: a particle is its
  rollback id, and which row it lives in is the program's business. For
  **every** live match the plain reference replays the generator's inputs
  from admission to the match's newest frame (integers decide who lives, so
  a replay from spawn is exact; no particle carries a rounding difference
  for longer than the 89 frames it lives) and is compared **by id**:
  ``reference.lifecycle_gap`` (ids alive on one side only + ``ttl``
  mismatches + allocators that differ, summed over the matches: 0),
  ``reference.frame_count_gap`` (0), and ``reference.translation_gap`` /
  ``reference.velocity_gap`` over the particles matched by id (and the
  emitters), under the configuration's limits. Two guarantees of its own:
  ``guarantee.spawn_fizzled`` (no birth ever found the world full) and
  ``guarantee.duplicate_live_ids`` (no two live rows of a match share an
  id). The replay steps all matches at once, a chunk a thread;
- a traced run's scalars carry what the metrics of the lifecycle read:
  ``live_entities`` (mean live particles a live match when the window
  closed), ``entity_births`` (ids minted a match-frame over the window),
  ``serve_carry_bytes``.
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
from benchmark.inputs import HeldKeys

REPLAY_CHUNK = 32       # matches the reference steps at once, a thread


class Driver(MatchServerDriver):
    def __init__(self, ctx: Context):
        self.plain_reference = ctx.reference
        super().__init__(dataclasses.replace(
            ctx, title=ctx.title.configured(ctx.config["settings"]),
            reference=_NoReplay))
        self._replaying = iter(())

    # -- set-up ---------------------------------------------------------

    def _world_of(self, match: int):
        return self.ctx.title.for_match(self.initial, int(self.seeds[match]))

    def _oracle(self):
        """A fresh serial singleton; inside ``check()`` the one of the next
        sampled match (``match_server.check`` builds one a sampled slot, in
        ``self.sample``'s order), from that match's own world."""
        from bevy_ggrs_tpu.runner import RollbackRunner

        match = next(self._replaying, None)
        s = self.ctx.config["settings"]
        return RollbackRunner(
            self.schedule,
            self.initial if match is None else self._world_of(match),
            int(s["max_prediction"]), self.players,
            self.ctx.title.input_spec())

    def setup(self, mark=lambda name: None) -> None:
        """``match_server``'s set-up with a world a match; the mix keeps
        every admitted match (no off-peak twin of this cell)."""
        from bevy_ggrs_tpu.serve.server import MatchServer
        from bevy_ggrs_tpu.utils.metrics import Metrics

        ctx, s = self.ctx, self.ctx.config["settings"]
        occ = ctx.traffic["occupancy"]
        admitted = int(occ["admit"])
        groups = int(s["stagger_groups"])
        if (admitted > self.capacity or int(occ["live"]) != admitted
                or admitted % groups):
            raise ValueError("occupancy does not fit the configuration")
        if ctx.trace:
            self.program_metrics = Metrics()
        self.schedule = ctx.title.make_schedule(ctx.control)
        self.initial = ctx.title.make_world(self.players)
        self.server = MatchServer(
            self.schedule, self.initial, int(s["max_prediction"]),
            self.players, ctx.title.input_spec(),
            capacity=self.capacity, stagger_groups=groups,
            num_branches=int(s["speculation_branches"]),
            spec_frames=int(s["speculation_frames"]),
            metrics=self.program_metrics,
        )
        mark("server_built")
        self.server.warmup()
        mark("server_warm")
        self.keys = HeldKeys(ctx.seed, admitted, self.players,
                             ctx.traffic["inputs"])
        self.keys.table(int(ctx.traffic["inputs_horizon_frames"]))
        rng = np.random.Generator(np.random.PCG64([ctx.seed, 0xC0FFEE]))
        self.seeds = rng.integers(0, 2 ** 32, size=admitted, dtype=np.uint32)
        self.live = {
            k: self.server.add_match(self._make_session(), self._feed(k),
                                     initial_state=self._world_of(k))
            for k in range(admitted)}
        mark("matches_admitted")
        # The serial oracle's executable compiles here, not after the window.
        self._oracle().warmup()
        mark("oracle_warm")
        for _ in range(int(ctx.traffic["warmup_frames"])):
            self.server.run_frame()
        self._block()
        self.sample = [int(k) for k in rng.choice(
            sorted(self.live), size=min(int(ctx.traffic["sample_slots"]),
                                        len(self.live)), replace=False)]
        if self.program_metrics is not None:
            self._lowering_scalars()

    def _lowering_scalars(self) -> None:
        """What warm-up fixed for good and the program said once, before
        the window the series readers look at."""
        carried = self.program_metrics.series.get("serve_carry_bytes")
        if carried:
            self.scalars["serve_carry_bytes"] = float(carried[-1])
        self.scalars["ring_row_lowering"] = {
            k: v for k, v in self.program_metrics.counters.items()
            if k.startswith("ring_row_lowering")}

    # -- the measured window --------------------------------------------

    def _ids_minted(self) -> int:
        """The sum of the live matches' id allocators (a device read)."""
        per_group = [np.asarray(g.states.resources["next_rollback_id"])
                     for g in self.server.groups]
        return int(sum(int(per_group[h.group][h.slot])
                       for h in self.live.values()))

    def window(self, seconds: float, pause_at=None, pause=None) -> float:
        minted0 = self._ids_minted()
        window_s = super().window(seconds, pause_at, pause)
        advanced = self.scalars["match_frames"]
        if advanced > 0:
            self.scalars["entity_births"] = (
                (self._ids_minted() - minted0) / advanced)
        return window_s

    def _counters(self) -> dict:
        out = super()._counters()
        for name in ("burst_steps_total", "burst_step_slots_total"):
            out[name] = sum(getattr(g, name) for g in self.server.groups)
        return out

    # -- after the window -----------------------------------------------

    def check(self) -> List[Comparison]:
        self._replaying = iter(self.sample)
        out = [c for c in super().check()
               if not c.name.startswith("reference.")]
        t = time.perf_counter()
        out += self._by_id()
        self.scalars["reference_s"] = time.perf_counter() - t
        return out

    def _replayed(self, ks, frames) -> list:
        """The reference's world of every match of ``ks`` after its own
        number of frames, as ``(world of a chunk, index in it)``."""
        ref, s = self.plain_reference, self.ctx.config["settings"]
        table = self.keys.table(int(frames.max()))

        def one(first):
            at = slice(first, first + REPLAY_CHUNK)
            return ref.replay_worlds(
                table[ks[at]], frames[at], self.seeds[ks[at]],
                rate=int(s["rate"]), capacity=int(s["world_capacity"]))

        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            worlds = list(pool.map(one, range(0, len(ks), REPLAY_CHUNK)))
        return [(worlds[i // REPLAY_CHUNK], i % REPLAY_CHUNK)
                for i in range(len(ks))]

    def _by_id(self) -> List[Comparison]:
        ref = self.plain_reference
        ks = np.asarray(list(self.live))
        frames = self._frames()
        got = [self.ctx.title.readback(g.states, self.players)[3]
               for g in self.server.groups]
        lifecycle = fizzled = duplicates = live_total = 0
        gap_n = gap_t = gap_v = 0.0
        wide = lambda x: np.asarray(x, np.float64)  # noqa: E731
        replayed = self._replayed(ks, frames)
        for k, frame, (world, i) in zip(ks, frames, replayed):
            h = self.live[int(k)]
            mine = {name: x[h.slot] for name, x in got[h.group].items()}
            rows = np.flatnonzero(mine["alive"])
            rows = rows[np.argsort(mine["id"][rows], kind="stable")]
            ids, ttl, pos, vel = (mine[name][rows] for name in (
                "id", "ttl", "position", "velocity"))
            want_ids, want_ttl, want_p, want_v = ref.by_id(world, i)
            live_total += ids.size
            duplicates += ids.size - np.unique(ids).size
            fizzled = max(fizzled, int(mine["fizzled"]))
            both, at, want_at = np.intersect1d(ids, want_ids,
                                               return_indices=True)
            lifecycle += (
                ids.size + want_ids.size - 2 * both.size
                + int((ttl[at] != want_ttl[want_at]).sum())
                + int(int(mine["next_id"]) != int(world["next_id"][i]))
                + int(int(mine["fizzled"]) != int(world["fizzled"][i])))
            gap_n = max(gap_n, abs(int(mine["frame_count"]) - int(frame)),
                        abs(int(world["frame_count"][i]) - int(frame)))
            gap_t = max(gap_t, float(np.abs(
                wide(mine["emitter"]) - world["emitter"][i]).max()))
            if both.size:
                gap_t = max(gap_t, float(np.abs(
                    wide(pos[at]) - want_p[want_at]).max()))
                gap_v = max(gap_v, float(np.abs(
                    wide(vel[at]) - want_v[want_at]).max()))
        self.scalars["live_entities"] = live_total / max(len(ks), 1)
        self.scalars["checked_matches"] = len(ks)
        self.scalars["checked_frames_each"] = [int(frames.min()),
                                               int(frames.max())]
        limits = limits_of(self.ctx.config)
        return [
            Comparison("guarantee.spawn_fizzled", fizzled, 0),
            Comparison("guarantee.duplicate_live_ids", duplicates, 0),
            Comparison("reference.lifecycle_gap", float(lifecycle), 0),
            Comparison("reference.frame_count_gap", float(gap_n), 0),
            Comparison("reference.translation_gap", gap_t,
                       limits["reference.translation_gap"]),
            Comparison("reference.velocity_gap", gap_v,
                       limits["reference.velocity_gap"]),
        ]

"""Loop kind ``match_server``: what an operator runs.

One ``MatchServer`` of one title, hosting SyncTest matches (every frame a
forced rollback and a checksum compare), driven in a closed loop:
``run_frame()`` back to back until the window closes. What it completed is
counted in match-frames: live matches times the frames each advanced.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

from benchmark.drivers.common import (
    Comparison, Context, DriverBase, limits_of, reference_gaps, tree_equal,
)
from benchmark.inputs import HeldKeys


class Driver(DriverBase):
    def __init__(self, ctx: Context):
        super().__init__(ctx, ["serve_frame_ms"])
        s = ctx.config["settings"]
        self.players = int(s["num_players"])
        self.capacity = int(s["capacity"])

    # -- set-up ---------------------------------------------------------

    def _make_session(self):
        from bevy_ggrs_tpu.session import SessionBuilder

        s = self.ctx.config["settings"]
        return (
            SessionBuilder(self.ctx.title.input_spec())
            .with_num_players(self.players)
            .with_max_prediction_window(int(s["max_prediction"]))
            .with_check_distance(int(s["check_distance"]))
            .start_synctest_session()
        )

    def _feed(self, match: int):
        keys = self.keys
        return lambda frame, handle: keys.bits(match, frame, handle)

    def _oracle(self):
        """A fresh serial singleton of the same schedule."""
        from bevy_ggrs_tpu.runner import RollbackRunner

        s = self.ctx.config["settings"]
        return RollbackRunner(
            self.schedule, self.initial, int(s["max_prediction"]),
            self.players, self.ctx.title.input_spec())

    def setup(self, mark=lambda name: None) -> None:
        from bevy_ggrs_tpu.serve.server import MatchServer
        from bevy_ggrs_tpu.utils.metrics import Metrics

        ctx, s = self.ctx, self.ctx.config["settings"]
        occ = ctx.traffic["occupancy"]
        admitted = int(occ["admit"])
        live = int(occ["live"])
        groups = int(s["stagger_groups"])
        if admitted > self.capacity or live > admitted or live % groups:
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
        handles = [self.server.add_match(self._make_session(), self._feed(k))
                   for k in range(admitted)]
        # Off-peak occupancy is what is left when matches end: the same
        # number of survivors in every group, which ones drawn from the seed.
        rng = np.random.Generator(np.random.PCG64([ctx.seed, 0xC0FFEE]))
        by_group = {}
        for k, h in enumerate(handles):
            by_group.setdefault(h.group, []).append(k)
        keep = set(range(admitted))
        if live < admitted:
            keep = set()
            for g, ks in sorted(by_group.items()):
                if len(ks) < live // groups:
                    raise ValueError("a group holds fewer matches than stay")
                keep.update(int(k) for k in rng.choice(
                    ks, size=live // groups, replace=False))
        for k, h in enumerate(handles):
            if k not in keep:
                self.server.retire_match(h)
        self.live = {k: handles[k] for k in sorted(keep)}
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

    def _block(self) -> None:
        import jax

        jax.block_until_ready([g.states for g in self.server.groups])

    def _slot(self, handle):
        return self.server.groups[handle.group].slots[handle.slot]

    def _frames(self) -> np.ndarray:
        return np.asarray([self._slot(h).frame for h in self.live.values()])

    # -- the measured window --------------------------------------------

    def window(self, seconds: float, pause_at=None, pause=None) -> float:
        """``run_frame()`` back to back for ``seconds``. Once ``pause_at``
        seconds have been measured, ``pause()`` runs between two frames (the
        traced run stops its profiler there), after the device has drained,
        and the time both took is taken out of the window."""
        server, annotate = self.server, self.ctx.annotate
        spans = self.series["serve_frame_ms"]
        self.open_counters()
        frames0 = self._frames()
        served0 = server.frames_served
        t0 = time.perf_counter()
        while True:
            ts = time.perf_counter()
            if ts - t0 >= seconds:
                break
            if pause is not None and ts - t0 >= pause_at:
                self._block()
                pause()
                pause = None
                t0 += time.perf_counter() - ts
                ts = time.perf_counter()
            with annotate("bench/run_frame"):
                server.run_frame()
            spans.append((time.perf_counter() - ts) * 1e3)
        with annotate("bench/final_wait"):
            self._block()
        end = time.perf_counter()
        served = server.frames_served - served0
        advanced = self._frames() - frames0
        self.attempted = served * len(self.live)
        self.failed = int(self.attempted - advanced.sum())
        self.close_counters()
        self.scalars["match_frames"] = int(advanced.sum())
        self.scalars["frames_served"] = served
        if spans:
            self.scalars["slowest_frame"] = {
                "index": int(np.argmax(spans)), "ms": float(max(spans))}
        self.scalars["live_matches"] = len(self.live)
        return end - t0

    def _counters(self) -> dict:
        sv = self.server
        tot = lambda name: sum(getattr(g, name) for g in sv.groups)  # noqa
        return {
            "slot_faults": sv.faults_total,
            "quarantined": sv.slots_quarantined + sv.slots_recovering,
            "evictions": sv.evictions_total,
            "device_dispatches_total": tot("device_dispatches_total"),
            "rollbacks_total": tot("rollbacks_total"),
            "spec_hits": tot("spec_hits"),
            "spec_partial_hits": tot("spec_partial_hits"),
            "spec_misses": tot("spec_misses"),
        }

    # -- after the window -----------------------------------------------

    def check(self) -> List[Comparison]:
        sv = self.server
        frames = self._frames()
        out = [
            Comparison("guarantee.match_frames_not_advanced",
                       float(self.failed), 0),
            Comparison("guarantee.slot_faults", sv.faults_total, 0),
            Comparison("guarantee.quarantined",
                       sv.slots_quarantined + sv.slots_recovering, 0),
            Comparison("guarantee.evictions", sv.evictions_total, 0),
            Comparison("guarantee.nothing_served",
                       float(self.scalars.get("match_frames", 0) <= 0), 0),
        ]
        # Sampled slots against fresh serial singletons, bitwise: state,
        # frame, ring frames and ring checksums.
        differ = 0
        for k in self.sample:
            h = self.live[k]
            session = self._make_session()
            oracle = self._oracle()
            feed = self._feed(k)
            for _ in range(self._slot(h).frame):
                for p in session.local_player_handles():
                    session.add_local_input(p, feed(session.current_frame, p))
                oracle.handle_requests(session.advance_frame(), session)
            core = sv.groups[h.group]
            same = (
                self._slot(h).frame == oracle.frame
                and tree_equal(core.slot_state(h.slot), oracle.state)
                and np.array_equal(np.asarray(core.rings.frames)[h.slot],
                                   np.asarray(oracle.ring.frames))
                and np.array_equal(np.asarray(core.rings.checksums)[h.slot],
                                   np.asarray(oracle.ring.checksums))
            )
            differ += int(not same)
        out.append(Comparison("guarantee.sampled_slots_differ_from_serial",
                              differ, 0))

        # Every live match against the plain reference.
        ks = list(self.live)
        horizon = int(frames.max())
        table = self.keys.table(horizon)[ks][:, :, :horizon]
        want_t, want_v, want_frames = self.ctx.reference.replay(table, frames)
        got = [self.ctx.title.readback(g.states, self.players)
               for g in sv.groups]
        idx = [(self.live[k].group, self.live[k].slot) for k in ks]
        got_t = np.stack([got[g][0][sl] for g, sl in idx])
        got_v = np.stack([got[g][1][sl] for g, sl in idx])
        got_frames = np.asarray([got[g][2][sl] for g, sl in idx])
        limits = limits_of(self.ctx.config)
        out.append(Comparison(
            "reference.frame_count_gap",
            float(np.abs(got_frames.astype(np.int64)
                         - want_frames.astype(np.int64)).max()), 0))
        out += [Comparison(name, gap, limits[name]) for name, gap in
                reference_gaps(got_t, got_v, want_t, want_v)]
        self.scalars["checked_matches"] = len(ks)
        self.scalars["checked_frames_each"] = [int(frames.min()), horizon]
        return out

    def cost_shapes(self) -> dict:
        """Shapes one batched dispatch works on, for the bytes functions."""
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
            "check_distance": int(self.ctx.config["settings"]["check_distance"]),
        }

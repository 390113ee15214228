"""The churning title hosted as P2P matches behind a network (PR 52).

``MatchServer.add_match(session, local_inputs, initial_state=)`` with hosted
P2P sessions AND a world a match: eight matches of the particle stress test
(4 births a frame in 1,024 rows: four leaves' rows reach ``FLAT_ROW_BYTES``,
so the rollout is carried as at the cell's size) in two stagger groups of
four slots, player 0 local on the server, player 1 a remote ``P2PSession``
over a lossy ``LoopbackNetwork`` whose far ends also lose everything they
send in ``LossBurst``s, some longer than the window. The harness IS the
benchmark's
loop kind ``match_server_p2p_churn`` (as ``tests/test_serve_boids_p2p.py``
drives ``match_server_p2p_world``), built from ``particles.wan``'s files at
the toy size with every far end resimulating serially, on a clock that moves
a millisecond a reading: a window is a number of served frames, a count or a
correctness fact and never a time.

What the program owes at this shape: a remote input that arrives late rolls
the match back through the batched burst, or commits a full or a partial hit
through the absorb out of a rollout carried as its scan wrote it (``STEPS``
and ``ONCE`` leaves), one lane committing while its neighbours do not; after
a drain every match's frame ``confirmed + 1`` is bitwise a serial
``RollbackRunner`` replay from that match's own spawn world and, by rollback
id, the plain NumPy reference's (the particles born in the mispredicted
frames stand where the confirmed input puts them; ids, ``ttl`` and the
allocator as if nothing had been mispredicted); the absorb's copies lie
under the device scope ``commit``; ``serve_burst_depth``,
``serve_absorb_commit_bytes`` and the counters beside them say what the
dispatches' plans say.
"""

import contextlib
import functools
import importlib

import numpy as np
import pytest

from benchmark import run
from benchmark.drivers import match_server_p2p
from benchmark.drivers.common import Context
from bevy_ggrs_tpu.fused import TickInts
from bevy_ggrs_tpu.state import FLAT_ROW_BYTES, ONCE, STEPS
from tests.test_serve_hosted_mesh import _Ticks

CELL = "particles.wan"
MATCHES, GROUPS = 8, 2
SLOTS = MATCHES // GROUPS


@functools.lru_cache(maxsize=None)
def served():
    """Drive the eight matches once; everything the tests read."""
    import jax

    from bevy_ggrs_tpu.serve import batch
    from bevy_ggrs_tpu.utils import xla_cache

    toy = run.load_toy(CELL)
    toy["traffic"].update(sample_slots=MATCHES)
    # A block of 14 bursts of 4 .. 10 frames: some pass the window of 8.
    toy["traffic"]["bursts"] = {"length_frames": [4, 10],
                                "gap_mean_frames": 60}
    _, _, config, traffic = run.load_cell(CELL, toy)
    title = importlib.import_module(f"benchmark.titles.{config['title']}")
    ctx = Context(
        config=config, traffic=traffic, seed=2**31 + 52, trace=True,
        control=None, title=title,
        annotate=lambda name: contextlib.nullcontext(),
        reference=importlib.import_module(
            f"benchmark.reference.{title.REFERENCE}"))
    d = importlib.import_module(
        f"benchmark.drivers.{config['driver']}").Driver(ctx)
    xla_cache.install_compile_listeners()
    plans = []      # (ABSORB_N, N_BURST) of every lane, a dispatch
    count = batch.BatchedSessionCore._count_lane_steps

    def recording(core, ints, branch_bits):
        plans.append((ints[:, TickInts.ABSORB_N].copy(),
                      ints[:, TickInts.N_BURST].copy()))
        return count(core, ints, branch_bits)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(match_server_p2p, "time", _Ticks())
        d.setup()
        metrics = d.program_metrics
        base = {k: len(v) for k, v in metrics.series.items()}
        counted = dict(metrics.counters)
        built0 = xla_cache.compile_counters()["backend_compiles"]
        patch.setattr(batch.BatchedSessionCore, "_count_lane_steps",
                      recording)
        d.window(1.0)       # 250 served frames: four readings a frame
        built = xla_cache.compile_counters()["backend_compiles"] - built0
        series = {k: list(v[base.get(k, 0):])
                  for k, v in metrics.series.items()}
        counters = {k: v - counted.get(k, 0)
                    for k, v in metrics.counters.items()}
    rows = {c.name: c for c in d.check()}   # the recorder is off again
    core = d.server.groups[0]
    return {"driver": d, "rows": rows, "built": built,
            "plans": plans, "series": series,
            "counters": counters, "row_bytes": core.row_bytes,
            "form": jax.tree_util.tree_leaves(core._exec.packed.form),
            "phases": xla_cache.executable_phases()}


def test_every_match_has_a_world_of_its_own_and_a_hosted_session():
    d = served()["driver"]
    assert len(set(d.seeds.tolist())) == MATCHES == len(d.hosts)
    assert d.sample == list(range(MATCHES))
    for k, far in enumerate(d.far):     # the far end's serial world too
        assert int(far.runner.state.resources["match_seed"]) == d.seeds[k]
        served_world = d.server.groups[d.live[k].group].slot_state(
            d.live[k].slot)
        assert int(served_world.resources["match_seed"]) == d.seeds[k]
    assert type(d.hosts[0]).__name__ == "P2PSession"


def test_hosted_particles_match_the_serial_replay_and_the_reference_by_id():
    r = served()
    d, rows = r["driver"], r["rows"]
    assert [n for n, c in rows.items() if not c.ok] == []
    for name in ("guarantee.desync_events",
                 "guarantee.sampled_matches_differ_from_serial_replay",
                 "guarantee.confirmed_frame_left_ring",
                 "guarantee.slot_faults", "guarantee.disconnects",
                 "guarantee.no_speculation_hit",
                 "guarantee.device_busy_outside_window",
                 "guarantee.spawn_fizzled", "guarantee.duplicate_live_ids",
                 "reference.lifecycle_gap", "reference.frame_count_gap"):
        assert rows[name].value == 0, name
    # particles_stress_server's limits as they stand; XLA:CPU contracts the
    # position update's multiply-add (tests/test_particles.py).
    assert rows["reference.translation_gap"].limit == 1e-4
    assert rows["reference.velocity_gap"].limit == 1e-4
    assert rows["reference.translation_gap"].value < 5e-5
    assert rows["reference.velocity_gap"].value < 5e-6
    assert d.scalars["checked_matches"] == MATCHES
    # Worlds at their steady population: 4 x (74.5 - 1) live a match.
    assert abs(d.scalars["live_entities"] - 4 * 73.5) < 32
    first, last = d.scalars["checked_frames_each"]
    assert 96 + 250 - 16 <= first <= last
    assert d._delta["checksum_ballots"] > MATCHES
    assert r["built"] == 0


def test_every_fate_of_a_rollback_and_a_withheld_frame_are_accounted():
    d = served()["driver"]
    c = d._delta
    assert c["spec_hits"] > 0 and c["spec_partial_hits"] > 0
    assert c["spec_misses"] > 0
    assert (c["spec_hits"] + c["spec_partial_hits"] + c["spec_misses"]
            == c["rollbacks_total"])
    assert d.withheld > 0 and c["frames_withheld"] == d.withheld
    assert d.failed == 0
    assert d.attempted == int(d.advanced.sum()) + d.withheld
    assert (d.advanced > 0).all()
    assert d.server.faults_total == 0 and d.server.evictions_total == 0


def test_a_lane_commits_a_flat_carried_row_while_its_neighbours_do_not():
    r = served()
    # A row the bursts carry flat, out of a rollout carried as written:
    # leaves with a branch axis behind the steps, and leaves without one.
    assert r["row_bytes"] >= FLAT_ROW_BYTES
    assert STEPS in r["form"] and ONCE in r["form"]
    lowering = r["driver"].scalars["ring_row_lowering"]
    assert any("carried" in k and v > 0 for k, v in lowering.items())
    commits = np.stack([a for a, _ in r["plans"]])       # [dispatch, lane]
    assert commits.shape[1] == SLOTS
    alone = ((commits > 0).sum(axis=1) == 1)
    assert alone.any()
    # Both kinds of hit went through the absorb: a commit of every step
    # the lane was asked for (full: no burst step left) and one with a tail
    # the burst re-runs (partial).
    bursts = np.stack([b for _, b in r["plans"]])
    hit = commits > 0
    assert (hit & (bursts == 0)).any() and (hit & (bursts > 0)).any()
    c = r["driver"]._delta
    assert (hit & (bursts == 0)).sum() == c["spec_hits"]
    assert (hit & (bursts > 0)).sum() == c["spec_partial_hits"]
    assert commits.max() > 1


def test_the_series_and_counters_say_what_the_plans_say():
    r = served()
    s, c, plans = r["series"], r["counters"], r["plans"]
    assert c["serve_dispatches_total"] == len(plans)
    assert s["serve_burst_depth"] == [float(b.max()) for _, b in plans]
    assert s["serve_absorb_depth"] == [float(a.max()) for a, _ in plans]
    committed = [float(a.sum()) * r["row_bytes"] for a, _ in plans]
    assert s["serve_absorb_commit_bytes"] == committed
    assert c["absorb_commit_bytes_total"] == sum(committed) > 0
    assert c["absorb_commit_bytes_total"] == (
        c["absorb_steps_total"] * r["row_bytes"])
    # The deepest lane's burst behind a network: past a SyncTest's 3 steps
    # now and then, one step in a dispatch nobody rolls back in.
    assert min(s["serve_burst_depth"]) <= 1 < 3 < max(s["serve_burst_depth"])


def test_a_hand_made_plan_is_counted_as_it_reads():
    from bevy_ggrs_tpu.utils.metrics import Metrics

    core = served()["driver"].server.groups[0]
    ints = TickInts.zeros(core.burst_frames, core.num_players, (SLOTS,))
    ints[:, TickInts.N_BURST] = [1, 6, 0, 2]
    ints[:, TickInts.ABSORB_N] = [0, 0, 3, 0]       # lane 2 commits alone
    sink = Metrics()
    with pytest.MonkeyPatch.context() as patch:     # the core as it was
        patch.setattr(core, "metrics", sink)
        patch.setattr(core, "burst_step_slots_total", 0)
        patch.setattr(core, "absorb_step_slots_total", 0)
        trees = core._host_args()[2]    # every branch of every lane alike
        core._count_lane_steps(ints, trees)
        ints[:, TickInts.ABSORB_N] = 0
        core._count_lane_steps(ints, trees)
        assert core.burst_step_slots_total == 2 * 6 * SLOTS
        assert core.absorb_step_slots_total == 3 * SLOTS
    assert sink.series["serve_burst_depth"] == [6.0, 6.0]
    assert sink.series["serve_absorb_depth"] == [3.0, 0.0]
    assert sink.series["serve_absorb_commit_bytes"] == [
        3.0 * core.row_bytes, 0.0]
    assert sink.counters["serve_dispatches_total"] == 2
    assert sink.counters["absorb_commit_bytes_total"] == 3 * core.row_bytes
    # the rollout's world-steps a lane: one a frame for trees that share
    # everything where the rollout shares (``rollout.py`` ``share_width``),
    # every branch every frame where it does not
    shares = core._exec.packed.share_width is not None
    assert sink.series["serve_rollout_steps"] == 2 * [float(
        core.spec_frames * (1 if shares else core.num_branches))]
    assert sink.series.get("serve_rollout_fill_share", []) == (
        [100.0, 100.0] if shares else [])
    assert sink.counters["absorb_step_slots_total"] == 3 * SLOTS


def test_the_absorbs_copies_lie_under_the_commit_scope():
    ops = served()["phases"][f"batched_tick_S{SLOTS}_B8_F8"]["ops"]
    under = [scopes for scopes in ops.values() if "commit" in scopes]
    assert under
    # ``commit`` lies inside the absorb phase and nowhere else, and holds
    # the matched branch's read and the main ring's writes.
    assert all(scopes[0] == "absorb" for scopes in under)
    inner = {s for scopes in under
             for s in scopes[scopes.index("commit") + 1:]}
    assert {"ring_read", "ring_write"} <= inner
    # The absorb phase's other side is not the commit's.
    assert any(scopes[0] == "absorb" and "commit" not in scopes
               for scopes in ops.values())

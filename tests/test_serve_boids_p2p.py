"""The entity-coupled title hosted as P2P matches behind a network (PR 47).

``MatchServer.add_match(session, local_inputs)`` with P2P sessions of a
64-boid world: eight matches in two stagger groups, player 0 local on the
server, player 1 a remote ``P2PSession`` over a lossy ``LoopbackNetwork``
whose far ends also lose everything they send in ``LossBurst``s, some longer
than the window. The harness IS the benchmark's loop kind
``match_server_p2p_world`` (as ``tests/test_serve_hosted_mesh.py`` drives
``match_server_mesh``), built from ``boids256.wan``'s files at the toy size
with every far end resimulating serially, on a clock that moves a
millisecond a reading, so a window is a number of served frames: a count or
a correctness fact, never a time.

What the program owes at this shape: after a drain every match's frame
``confirmed + 1`` bitwise a serial ``RollbackRunner`` replay of the
confirmed inputs; each held confirmed step within the configuration's limits
of the plain reference's step of the program's own state; every fate of a
rollback (full hit, partial hit, miss) and a withheld frame met and
accounted; lanes that commit through the absorb phase, whose depth is the
series ``serve_absorb_depth``, a sample a dispatch; and the checksums of the
frames the server absorbed or stepped equal to the far end's serial ones.
"""

import contextlib
import functools
import importlib

import numpy as np
import pytest

from benchmark import run
from benchmark.drivers import match_server_p2p
from benchmark.drivers.common import Context
from tests.test_serve_hosted_mesh import _Ticks

CELL = "boids256.wan"
MATCHES, GROUPS = 8, 2


@functools.lru_cache(maxsize=None)
def served():
    """Drive the eight matches once; everything the tests read."""
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
        config=config, traffic=traffic, seed=2**31 + 47, trace=True,
        control=None, title=title,
        annotate=lambda name: contextlib.nullcontext(),
        reference=importlib.import_module(
            f"benchmark.reference.{title.REFERENCE}"))
    d = importlib.import_module(
        f"benchmark.drivers.{config['driver']}").Driver(ctx)
    xla_cache.install_compile_listeners()
    commits = []        # n_commit of every rollback the core accounted
    account = batch.account_rollback

    def recording(owner, load_frame, n_steps, branch, n_commit, *a, **k):
        commits.append(n_commit)
        return account(owner, load_frame, n_steps, branch, n_commit, *a, **k)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(match_server_p2p, "time", _Ticks())
        d.setup()
        series = d.program_metrics.series
        base = {k: len(v) for k, v in series.items()}
        built0 = xla_cache.compile_counters()["backend_compiles"]
        patch.setattr(batch, "account_rollback", recording)
        d.window(1.0)       # 250 served frames: four readings a frame
        built = xla_cache.compile_counters()["backend_compiles"] - built0
        window_series = {k: list(v[base.get(k, 0):])
                         for k, v in series.items()}
        rows = {c.name: c for c in d.check()}
    return {"driver": d, "rows": rows, "built": built, "commits": commits,
            "series": window_series}


def test_hosted_boids_match_the_serial_replay_and_the_reference():
    r = served()
    d, rows = r["driver"], r["rows"]
    assert [n for n, c in rows.items() if not c.ok] == []
    assert d.sample == list(range(MATCHES))
    for name in ("guarantee.desync_events",
                 "guarantee.sampled_matches_differ_from_serial_replay",
                 "guarantee.confirmed_frame_left_ring",
                 "guarantee.slot_faults", "guarantee.disconnects",
                 "guarantee.no_speculation_hit",
                 "guarantee.device_busy_outside_window",
                 "reference.frame_count_gap"):
        assert rows[name].value == 0, name
    # boids_1k_server256's limits as they stand.
    assert rows["reference.translation_gap"].limit == 4e-4
    assert rows["reference.velocity_gap"].limit == 8e-5
    assert 0 < rows["reference.translation_gap"].value < 1e-5
    # Every match sampled: every held confirmed step of each was stepped
    # by the reference (a ring of window + 1 rows: up to 8 steps a match).
    assert d.scalars["anchored_matches"] == MATCHES
    assert 4 * MATCHES <= d.scalars["anchored_steps"] <= 8 * MATCHES
    assert d._delta["checksum_ballots"] > MATCHES
    assert r["built"] == 0


def test_every_fate_of_a_rollback_and_a_withheld_frame_are_accounted():
    d = served()["driver"]
    c = d._delta
    assert c["spec_hits"] > 0 and c["spec_partial_hits"] > 0
    assert c["spec_misses"] > 0
    assert (c["spec_hits"] + c["spec_partial_hits"] + c["spec_misses"]
            == c["rollbacks_total"])
    # Bursts longer than the window: sessions did raise PredictionThreshold,
    # the server counted each once and the benchmark's own count agrees.
    assert d.withheld > 0 and c["frames_withheld"] == d.withheld
    assert d.failed == 0
    assert d.attempted == int(d.advanced.sum()) + d.withheld
    assert (d.advanced > 0).all()
    assert d.server.faults_total == 0 and d.server.evictions_total == 0


def test_absorb_depth_has_a_sample_a_dispatch_and_the_deepest_commit():
    r = served()
    c, depth = r["driver"]._delta, r["series"]["serve_absorb_depth"]
    slots = MATCHES // GROUPS
    assert len(depth) == c["device_dispatches_total"]
    assert c["absorb_steps_total"] == sum(r["commits"]) > 0
    assert max(depth) == max(r["commits"]) > 1
    # Each dispatch ran its deepest lane's commit for every lane.
    assert sum(depth) * slots == c["absorb_step_slots_total"]
    assert 0 < sum(v > 0 for v in depth) < len(depth)
    hits = sum(n > 0 for n in r["commits"])
    assert hits == c["spec_hits"] + c["spec_partial_hits"]


def test_absorbed_frames_checksums_equal_the_far_ends_serial_ones():
    """Each far end resimulates serially; after the drain and one more tick
    of the far ends both rings hold the newest confirmed frames, the
    server's partly absorbed from branch rows or stepped by the burst:
    checksum by checksum the same."""
    d = served()["driver"]
    # The drain let every input land but advanced no far end: one tick of
    # each makes it resimulate what it had predicted of the server's.
    d.net.advance(d.dt)
    for far in d.far:
        far.tick()
    compared = 0
    for k, h in d.live.items():
        upto = d._confirmed_upto()[k]
        mine = d.server.groups[h.group].slot_ring(h.slot)
        theirs = d.far[k].runner.ring
        at = lambda ring: {  # noqa: E731
            int(f): np.asarray(ring.checksums)[row]
            for row, f in enumerate(np.asarray(ring.frames))}
        a, b = at(mine), at(theirs)
        both = [f for f in a if f in b and 0 <= f <= min(
            upto, d.far[k].session.confirmed_frame() + 1)]
        assert both, k
        for f in both:
            assert np.array_equal(a[f], b[f]), (k, f)
        compared += len(both)
    assert compared >= 2 * MATCHES

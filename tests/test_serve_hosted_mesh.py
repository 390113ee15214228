"""Hosted lobbies on the normal served path: ``MatchServer.add_match(session,
local_inputs)`` with a P2P session of MORE than two players, a window other
than 8 and an input delay: eight players at window 12 / delay 2 (upstream's
``box_game_p2p.rs`` settings), and four at window 8 / delay 1 so that the
eight are not the only other point.

Four lobbies in two stagger groups at 16 and at 80 branches, player 0 local on the
server and every other player a remote ``P2PSession`` of its own in a full
mesh over a lossy ``LoopbackNetwork`` whose far ends also lose everything
they send in bursts, some longer than window + delay. The harness IS the
benchmark's loop kind ``match_server_mesh`` (as ``tests/test_mesh_spectator``
drives ``p2p_mesh``), built from ``hosted8.wan``'s files with the shape
overridden, on a clock that moves a millisecond a reading so that both data
planes serve exactly the same frames; a count or a correctness fact, never a
time.

What the program owes at these shapes, on the native batch plane and on the
per-slot Python path alike: zero desyncs with ballots compared; every
lobby's state at its newest confirmed-and-held frame bitwise a serial
``RollbackRunner`` replay of the delay-shifted inputs and within the plain
reference's limits; a frame withheld by back-pressure neither advances nor
fails; the two planes bitwise each other; the series
``serve_endpoints_polled``; no executable after warm-up.
"""

import contextlib
import functools
import importlib

import numpy as np
import pytest

from benchmark import run
from benchmark.drivers import match_server_p2p
from benchmark.drivers.common import Context
from bevy_ggrs_tpu.native import core as ncore

CELL = "hosted8.wan"
LOBBIES, GROUPS = 4, 2
# players, window, input delay, branches: 16 are read back by the select
# chain, 80 (past ``state.SELECT_ROWS``) by the one-hot pass.
SHAPES = {
    "8p_w12_d2": (8, 12, 2, 16),
    "4p_w8_d1": (4, 8, 1, 80),
}
NEEDS_NATIVE = pytest.mark.skipif(
    not ncore.available(), reason="native session core did not build")
CASES = [pytest.param(plane, shape, id=f"{plane}-{shape}",
                      marks=[NEEDS_NATIVE] if plane == "native" else [])
         for shape in SHAPES for plane in ("native", "python")]


class _Ticks:
    """``time`` for the driver's window loop: a millisecond a reading, so
    a window is a number of served frames and not a stretch of this
    machine's time."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self) -> float:
        self.now += 1e-3
        return self.now


@functools.lru_cache(maxsize=None)
def served(plane, shape):
    """Drive the four lobbies once a (plane, shape); everything the tests
    read."""
    from bevy_ggrs_tpu import spec_runner
    from bevy_ggrs_tpu.serve import batch
    from bevy_ggrs_tpu.utils import xla_cache

    players, window, delay, branches = SHAPES[shape]
    toy = run.load_toy(CELL)
    toy["config"]["settings"].update(
        num_players=players, max_prediction=window, input_delay=delay,
        speculation_frames=window, speculation_branches=branches,
        capacity=LOBBIES, stagger_groups=GROUPS)
    toy["traffic"].update(
        occupancy={"admit": LOBBIES, "live": LOBBIES}, sample_slots=LOBBIES)
    # A block of 14 bursts of 4 .. 17 frames: some pass window + delay.
    toy["traffic"]["bursts"] = {"length_frames": [4, 17],
                                "gap_mean_frames": 60}
    _, _, config, traffic = run.load_cell(CELL, toy)
    title = importlib.import_module(f"benchmark.titles.{config['title']}")
    ctx = Context(
        config=config, traffic=traffic, seed=2**31 + 39 + players,
        trace=True, control=None, title=title,
        annotate=lambda name: contextlib.nullcontext(),
        reference=importlib.import_module(
            f"benchmark.reference.{title.REFERENCE}"))
    d = importlib.import_module(
        f"benchmark.drivers.{config['driver']}").Driver(ctx)
    xla_cache.install_compile_listeners()
    with pytest.MonkeyPatch.context() as patch:
        # One program a tick for the resimulating far ends, as
        # tests/conftest.py pins the suite (this outlives that patch).
        patch.setattr(spec_runner, "_blocking_ms", lambda call, reps=3: 0.0)
        if plane == "python":
            # The GGRS_NO_NATIVE=1 route of ``_dispatch``.
            patch.setattr(batch.native_spec, "make_batch_plane",
                          lambda *a, **k: None)
        patch.setattr(match_server_p2p, "time", _Ticks())
        d.setup()
        series = d.program_metrics.series
        base = {k: len(v) for k, v in series.items()}
        withheld0 = d.server.frames_withheld_total
        built0 = xla_cache.compile_counters()["backend_compiles"]
        d.window(1.0)
        built = xla_cache.compile_counters()["backend_compiles"] - built0
        window_series = {k: list(v[base.get(k, 0):])
                         for k, v in series.items()}
        withheld_by_server = d.server.frames_withheld_total - withheld0
        rows = {c.name: c for c in d.check()}
    return {
        "driver": d, "rows": rows, "built": built, "series": window_series,
        "withheld_by_server": withheld_by_server,
        "frames": d._frames().tolist(),
        "rings": [(np.asarray(g.rings.frames), np.asarray(g.rings.checksums))
                  for g in d.server.groups],
    }


@pytest.mark.parametrize("plane,shape", CASES)
def test_hosted_lobbies_match_the_serial_replay_and_the_reference(plane, shape):
    r = served(plane, shape)
    d, rows = r["driver"], r["rows"]
    assert [n for n, c in rows.items() if not c.ok] == []
    # Every lobby was replayed serially, bitwise, and met the reference.
    assert d.sample == list(range(LOBBIES))
    assert d.scalars["checked_matches"] == LOBBIES
    for name in ("guarantee.desync_events",
                 "guarantee.sampled_matches_differ_from_serial_replay",
                 "guarantee.inputs_differ_from_shifted_table",
                 "guarantee.confirmed_frame_left_ring",
                 "guarantee.slot_faults", "guarantee.disconnects",
                 "reference.frame_count_gap"):
        assert rows[name].value == 0, name
    assert rows["reference.translation_gap"].limit == 1e-4
    assert rows["reference.velocity_gap"].limit == 1e-6
    assert d.scalars["inputs_compared"] > LOBBIES * SHAPES[shape][0]
    assert d._delta["checksum_ballots"] > LOBBIES
    # Late remote input did roll back, and each rollback met one fate.
    c = d._delta
    assert c["rollbacks_total"] > 10 * LOBBIES
    assert (c["spec_hits"] + c["spec_partial_hits"] + c["spec_misses"]
            == c["rollbacks_total"])
    assert r["built"] == 0


@pytest.mark.parametrize("plane,shape", CASES)
def test_a_withheld_frame_neither_advances_nor_fails(plane, shape):
    r = served(plane, shape)
    d = r["driver"]
    # Bursts longer than window + delay: the server's sessions did raise
    # PredictionThreshold, the server counted each once, and the benchmark's
    # own count agrees.
    assert d.withheld > 0
    assert r["withheld_by_server"] == d.withheld
    assert d.failed == 0
    assert d.attempted == int(d.advanced.sum()) + d.withheld
    assert (d.advanced > 0).all()
    assert d.server.faults_total == 0 and d.server.evictions_total == 0


@pytest.mark.parametrize("plane,shape", CASES)
def test_endpoints_polled_has_one_sample_a_group_tick(plane, shape):
    r = served(plane, shape)
    polled = r["series"]["serve_endpoints_polled"]
    assert len(polled) == len(r["series"]["serve_poll_ms"]) > 100
    # A hosted lobby polls one endpoint a remote player; two lobbies a group.
    remote = SHAPES[shape][0] - 1
    assert set(polled) == {float(remote * LOBBIES // GROUPS)}
    assert r["driver"].scalars["remote_endpoints"] == remote * LOBBIES
    # The tree builds are timed on both planes, one sample a dispatch.
    assert (len(r["series"]["serve_branch_build_ms"])
            == len(r["series"]["serve_arg_assembly_ms"]) > 100)


@NEEDS_NATIVE
@pytest.mark.parametrize("shape", list(SHAPES))
def test_native_plane_and_python_path_agree_bitwise(shape):
    nat, py = served("native", shape), served("python", shape)
    assert nat["driver"].server.groups[0]._plane is not None
    assert py["driver"].server.groups[0]._plane is None
    assert nat["frames"] == py["frames"]
    assert nat["driver"].withheld == py["driver"].withheld
    for key in ("rollbacks_total", "spec_hits", "spec_partial_hits",
                "spec_misses", "burst_steps_total", "checksum_ballots",
                "device_dispatches_total"):
        assert nat["driver"]._delta[key] == py["driver"]._delta[key], key
    for (fa, ca), (fb, cb) in zip(nat["rings"], py["rings"]):
        assert np.array_equal(fa, fb) and np.array_equal(ca, cb)

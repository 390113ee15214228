"""What PR 52 adds to the benchmark as code: loop kind
``match_server_p2p_churn`` (``match_server_p2p_world``'s loop with
``match_server_churn``'s world a match and its comparison by rollback id, on
confirmed frames only) and the three metric files.

The cell itself (``particles.wan``) is rehearsed end to end, traced and under
both controls by ``test_benchmark_rehearsal.py``, which takes its cases from
``BENCHMARK.json``; the program at the shape is held by
``tests/test_particles_p2p.py``. Here: the reference half of ``check()`` on
rings the plain reference itself stepped and laid out in rows in another
order, whose rows past a match's confirmed frame hold a misprediction
(float32: passes, because those rows are never read; both controls and every
lifecycle fault: fail, by the rows ``test_benchmark_particles.py`` names);
the same on a live driver whose hosts were forced ahead of their far ends;
the order of the window's loop, with recording stand-ins; the metric files.
"""

import contextlib
import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import run
from benchmark.drivers import match_server_p2p
from benchmark.drivers.common import Context
from benchmark.drivers.match_server_p2p_churn import Driver
from benchmark.drivers.match_server_p2p_world import _DrainedFrames
from benchmark.readers.common import Results
from benchmark.reduce import trace as rt
from benchmark.reference import particles_np as ref
from benchmark.titles import particles as title
from bevy_ggrs_tpu.state import WorldState
from bevy_ggrs_tpu.utils import xla_cache
from tests.benchmark.test_benchmark_particles import (
    MASKS, _age_a_particle, _fizzle, _lose_a_particle, _mint_an_id_twice,
    _skip_an_id,
)
from tests.test_serve_hosted_mesh import _Ticks

CELL = "particles.wan"
NEW_METRICS = {
    "scope_commit_ms.serve": [CELL, "boids256.wan"],
    "burst_depth.p50.serve": [CELL, "boids256.wan", "particles.synctest",
                              "boids256.synctest"],
    "absorb_commit_bytes.serve": [CELL, "boids256.wan"],
}


def _context(control=None, seed=2**31 + 52, trace=False):
    _, _, config, traffic = run.load_cell(CELL, run.load_toy(CELL))
    return Context(config=config, traffic=traffic, seed=seed, trace=trace,
                   control=control, title=title, reference=ref,
                   annotate=lambda name: contextlib.nullcontext())


def test_the_cell_is_the_churning_title_behind_the_hosted_network():
    """``particles_stress_server``'s server, title and limits with
    ``boids_1k_server256_p2p``'s sessions, under ``wan_bursts``'s numbers
    and ``synctest_filled``'s warm-up."""
    _, cell, config, traffic = run.load_cell(CELL)
    churn, filled = run.load_cell("particles.synctest")[2:]
    hosted, bursts = run.load_cell("boids256.wan")[2:]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "particles_stress_server_p2p", "wan_bursts_filled", 1)
    assert (config["driver"], config["title"]) == (
        "match_server_p2p_churn", "particles")
    s = config["settings"]
    # No width differs from the SyncTest deployment; the session knobs are
    # the hosted deployment's (SyncTest's check distance has no place).
    for key, value in churn["settings"].items():
        if key != "check_distance":
            assert s[key] == value, key
    for key in ("fps", "input_delay", "disconnect_timeout_s",
                "desync_detection", "max_prediction", "capacity",
                "stagger_groups", "speculation_branches",
                "speculation_frames"):
        assert s[key] == hosted["settings"][key], key
    assert set(s) == (set(churn["settings"]) - {"check_distance"}) | {
        "disconnect_timeout_s", "desync_detection"}
    assert {k: v["limit"] for k, v in config["limits"].items()} == {
        k: v["limit"] for k, v in churn["limits"].items()}
    assert set(config["reduced"]) == set(hosted["reduced"]) == {
        "far_end_session_only", "device_drained_each_frame"}
    assert config["architecture"] is None
    for key in ("loop", "occupancy", "network", "bursts", "inputs",
                "sync_frames_limit", "sample_slots"):
        assert traffic[key] == bursts[key], key
    assert traffic["warmup_frames"] == filled["warmup_frames"] == 96
    assert traffic["inputs"] == filled["inputs"]


def test_every_match_is_seeded_from_the_seed_and_by_nothing_else():
    a, b, other = (Driver(_context(seed=n)) for n in (5, 5, 6))
    assert a.seeds.dtype == np.uint32 and a.seeds.shape == (8,)
    assert len(set(a.seeds.tolist())) == 8
    assert np.array_equal(a.seeds, b.seeds)
    assert not np.array_equal(a.seeds, other.seeds)


# ---------------------------------------------------------------------------
# The comparison by id on confirmed frames only, on rings the reference
# stepped
# ---------------------------------------------------------------------------

MATCHES, DEPTH, FRAMES, AHEAD = 4, 9, 110, 3
UPTO = FRAMES - 1 - AHEAD   # the newest frame that rests on confirmed inputs


def _rows_of(worlds, rng, capacity):
    """``worlds`` as a program's state would hold them: each match laid out
    in ``capacity`` rows in an order of its own, dead rows filled with
    junk (``test_benchmark_particles._program_side``'s layout)."""
    out = {"alive": np.zeros((MATCHES, capacity), bool),
           "id": np.full((MATCHES, capacity), -1, np.int32),
           "ttl": rng.randint(0, 9, (MATCHES, capacity)).astype(np.int32),
           "position": rng.uniform(-9, 9, (MATCHES, capacity, 2)).astype(
               np.float32),
           "velocity": rng.uniform(-9, 9, (MATCHES, capacity, 2)).astype(
               np.float32)}
    for m in range(MATCHES):
        ids, ttl, pos, vel = ref.by_id(worlds, m)
        rows = rng.permutation(capacity)[:ids.size]
        out["alive"][m, rows] = True
        out["id"][m, rows], out["ttl"][m, rows] = ids, ttl
        out["position"][m, rows], out["velocity"][m, rows] = pos, vel
    for name in ("next_id", "fizzled", "frame_count", "emitter"):
        out[name] = np.array(worlds[name])
    return out


def _stepped_rings(table, seeds, control, spoil, rate, capacity):
    """``[MATCHES, DEPTH]`` ring rows of the last ``DEPTH`` of ``FRAMES``
    frames a match, stepped by the plain reference under ``control``; row
    ``f % DEPTH`` holds the world entering frame f. The last ``AHEAD``
    frames stand for a host ahead of its far end: their rows were stepped
    through a mispredicted remote input (the emitter of player 1 went
    another way, and the particles born since stand beside it). ``spoil``
    is let at the row of frame ``UPTO``."""
    worlds = ref.spawn(seeds, 2, rate)
    home = ref.emitter_spawn(2)
    rng = np.random.RandomState(4)
    ring = None
    for f in range(FRAMES):
        if f >= FRAMES - DEPTH:
            mine = _rows_of(worlds, rng, capacity)
            if f == UPTO and spoil is not None:
                spoil(mine)
            if ring is None:
                ring = {k: np.zeros((MATCHES, DEPTH) + v.shape[1:], v.dtype)
                        for k, v in mine.items()}
            for k, v in mine.items():
                ring[k][:, f % DEPTH] = v
        bits = table[:, :, f].copy()
        if f >= UPTO:       # the remote input, mispredicted
            bits[:, 1] = np.where(bits[:, 1] == 8, 4, 8)
        worlds = ref.step(worlds, bits,
                          "bfloat16" if control == "bf16_state" else
                          "float32", capacity)
        if control == "freeze_last_player":
            worlds["emitter"][:, 1] = home[1]
    frames = np.full((MATCHES, DEPTH), -1, np.int32)
    for f in range(FRAMES - DEPTH, FRAMES):
        frames[:, f % DEPTH] = f
    return ring, frames


def _driver_on_stepped_rings(control=None, spoil=None):
    driver = Driver(_context(control))
    s = driver.ctx.config["settings"]
    table = np.random.RandomState(3).choice(MASKS, size=(MATCHES, 2, FRAMES))
    driver.keys = types.SimpleNamespace(table=lambda horizon: table)
    driver.seeds = np.asarray([11, 22, 33, 44], np.uint32)
    ring, frames = _stepped_rings(table, driver.seeds, control, spoil,
                                  int(s["rate"]), int(s["world_capacity"]))
    groups = []
    for g in range(2):      # matches 0-1 in group 0, 2-3 in group 1
        rows = slice(2 * g, 2 * g + 2)
        groups.append(types.SimpleNamespace(
            slots=[types.SimpleNamespace(frame=FRAMES)] * 2,
            rings=types.SimpleNamespace(
                frames=frames[rows],
                states=WorldState(
                    alive=ring["alive"][rows], rollback_id=ring["id"][rows],
                    components={k: ring[k][rows]
                                for k in ("ttl", "position", "velocity")},
                    present={},
                    resources={
                        "next_rollback_id": ring["next_id"][rows],
                        "spawn_fizzled": ring["fizzled"][rows],
                        "frame_count": ring["frame_count"][rows],
                        "emitter_position": ring["emitter"][rows]}))))
    driver.server = types.SimpleNamespace(groups=groups)
    driver.live = {k: types.SimpleNamespace(group=k // 2, slot=k % 2)
                   for k in range(MATCHES)}
    # A host at frame FRAMES whose far end confirmed all but the last
    # AHEAD + 1 inputs.
    driver.hosts = [types.SimpleNamespace(
        current_frame=FRAMES,
        confirmed_frame=lambda: UPTO - 1)] * MATCHES
    return driver, int(s["rate"])


@pytest.mark.parametrize("control,spoil,failed", [
    (None, None, set()),
    ("bf16_state", None, {"reference.translation_gap",
                          "reference.velocity_gap"}),
    ("freeze_last_player", None, {"reference.translation_gap"}),
    (None, _lose_a_particle, {"reference.lifecycle_gap"}),
    (None, _age_a_particle, {"reference.lifecycle_gap"}),
    (None, _skip_an_id, {"reference.lifecycle_gap"}),
    (None, _mint_an_id_twice, {"reference.lifecycle_gap",
                               "guarantee.duplicate_live_ids"}),
    (None, _fizzle, {"reference.lifecycle_gap", "guarantee.spawn_fizzled"}),
])
def test_by_id_on_confirmed_frames_sees_both_controls_and_every_fault(
        control, spoil, failed):
    driver, rate = _driver_on_stepped_rings(control, spoil)
    view = driver._confirmed_rows()
    # The view holds a match's row of frame UPTO and nothing later; the
    # rings themselves are left as they were.
    for group in view.server.groups:
        assert np.array_equal(group.states.resources["frame_count"],
                              [UPTO, UPTO])
        assert group.states.alive.shape == (2, 1024)
    assert np.array_equal(view._frames(), [UPTO] * MATCHES)
    rows = view._by_id()
    assert [c.name for c in rows] == [
        "guarantee.spawn_fizzled", "guarantee.duplicate_live_ids",
        "reference.lifecycle_gap", "reference.frame_count_gap",
        "reference.translation_gap", "reference.velocity_gap"]
    assert {c.name for c in rows if not c.ok} == failed
    assert driver.scalars["checked_matches"] == MATCHES
    assert driver.scalars["checked_frames_each"] == [UPTO, UPTO]
    assert abs(driver.scalars["live_entities"] - rate * 73.5) < rate * 8
    if control is None and spoil is None:
        # The reference against itself, rows shuffled: nothing differs.
        assert all(c.value == 0 for c in rows)


def test_a_row_past_the_confirmed_frame_would_fail_the_sound_run():
    """The same rings with the hosts' newest row taken for confirmed: the
    particles born under the misprediction stand beside the wrong emitter,
    which is what the view is for. The lifecycle is input-free: exact."""
    driver, _ = _driver_on_stepped_rings()
    driver.hosts = [types.SimpleNamespace(
        current_frame=FRAMES, confirmed_frame=lambda: FRAMES)] * MATCHES
    rows = {c.name: c for c in driver._confirmed_rows()._by_id()}
    assert {n for n, c in rows.items() if not c.ok} == {
        "reference.translation_gap"}
    assert rows["reference.translation_gap"].value > 0.05
    assert driver.scalars["checked_frames_each"] == [FRAMES - 1, FRAMES - 1]
    # A match whose confirmed frame left its ring has another frame's row
    # there (``check()`` never gets this far:
    # ``guarantee.confirmed_frame_left_ring`` fails the run first).
    driver.hosts = list(driver.hosts)
    driver.hosts[2] = types.SimpleNamespace(
        current_frame=FRAMES, confirmed_frame=lambda: FRAMES - DEPTH - 2)
    rows = {c.name: c for c in driver._confirmed_rows()._by_id()}
    assert not rows["reference.frame_count_gap"].ok


# ---------------------------------------------------------------------------
# A live driver at the toy size
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def hosted():
    """The cell's driver at its toy size, set up (every session RUNNING,
    warm-up served) and not yet measured."""
    d = Driver(_context(trace=True))
    d.setup()
    return d


def test_setup_admits_a_world_a_match_and_meets_a_hit_before_the_window(
        hosted):
    d = hosted
    assert d.initial.components["position"].shape == (1024, 2)
    assert len(d.live) == len(d.hosts) == len(d.far) == 8
    assert sum(f.runner is not None for f in d.far) == len(d.sample) == 2
    # The set-up's view of the server is gone; every slot holds the world
    # of its match's seed, and so does a sampled far end's serial runner.
    assert type(d.server).__name__ == "MatchServer"
    for k, h in d.live.items():
        world = d.server.groups[h.group].slot_state(h.slot)
        assert int(world.resources["match_seed"]) == d.seeds[k]
    for k in d.sample:
        assert int(d.far[k].runner.state.resources["match_seed"]) == (
            d.seeds[k])
    assert d.drained and d.scalars["serve_carry_bytes"] > 0
    assert any("carried" in k for k in d.scalars["ring_row_lowering"])
    # What ``guarantee.no_speculation_hit`` asks, met by the warm-up.
    assert d._total("spec_hits") > 0 and d._total("absorb_steps_total") > 0
    shapes = d.cost_shapes()
    assert shapes["slot_rings_bytes"] > shapes["slot_states_bytes"] > 0
    assert "num_entities" not in shapes
    from benchmark.costs import batched_tick

    assert batched_tick.least_bytes(shapes) > 0


def test_every_stop_of_the_clock_follows_a_completed_block(
        hosted, monkeypatch):
    d, events = hosted, []
    block, far_ends, frame = d._block, d._far_ends, d._frame_to_its_end

    def recording_block():
        block()
        events.append("block")

    def recording_far_ends():
        events.append("far_ends")
        return far_ends()

    def recording_frame(server):
        events.append("frame")
        assert not isinstance(server, _DrainedFrames)
        frame(server)

    monkeypatch.setattr(match_server_p2p, "time", _Ticks())
    monkeypatch.setattr(d, "_block", recording_block)
    monkeypatch.setattr(d, "_far_ends", recording_far_ends)
    monkeypatch.setattr(d, "_frame_to_its_end", recording_frame)
    d.window(0.05)
    served = d.scalars["frames_served"]
    assert served >= 10 and not isinstance(d.server, _DrainedFrames)
    assert events == ["far_ends", "frame", "block"] * served + ["block"]
    assert d.busy_stops == 0 and d.drained
    assert len(d.series["device_drain_ms"]) == served
    assert len(d.series["serve_frame_ms"]) == served
    # A loop whose wait does not complete is counted, stop by stop.
    events.clear()
    monkeypatch.setattr(d, "_block", lambda: events.append("no block"))
    d.window(0.05)
    assert d.busy_stops == d.scalars["frames_served"] - 1 > 0
    monkeypatch.undo()
    d._block()


def test_no_row_past_the_confirmed_frame_with_hosts_forced_ahead(
        hosted, monkeypatch):
    d = hosted
    for _ in range(5):          # served frames the far ends do not answer
        d.net.advance(d.dt)
        d.server.run_frame()
    d._block()
    upto = d._confirmed_upto()
    lead = [d.hosts[k].current_frame - 1 - upto[k] for k in d.live]
    assert max(lead) >= 5
    view = d._confirmed_rows()
    for k, h in d.live.items():
        count = view.server.groups[h.group].states.resources["frame_count"]
        assert int(count[h.slot]) == upto[k] < d.hosts[k].current_frame
    assert np.array_equal(view._frames(), [upto[k] for k in d.live])
    rows = view._by_id()
    assert [c.name for c in rows if not c.ok] == []
    assert d.scalars["checked_frames_each"] == [min(upto.values()),
                                                max(upto.values())]
    # The far ends catch up, and a window's whole check passes, twice (the
    # serial oracle is handed each sampled match's own world anew).
    monkeypatch.setattr(match_server_p2p, "time", _Ticks())
    d.window(0.05)
    assert [c.name for c in d.check() if not c.ok] == []
    assert [c.name for c in d.check() if not c.ok] == []


# ---------------------------------------------------------------------------
# The metric files
# ---------------------------------------------------------------------------


def _read(name, results):
    with open(os.path.join(run.HERE, "layer_metrics", name + ".json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    return importlib.import_module(
        f"benchmark.readers.{spec['kind']}").read(spec, results)


def _traced(ops):
    """A made-up traced stretch of two dispatches of a tick whose phase
    map is ``ops``."""
    spans = [(rt.WINDOW_SPAN, 0.0, 10.0)]
    programs = [("jit__tick_impl(7)", 1.0, 1.014),
                ("jit__tick_impl(7)", 3.0, 3.014)]
    trace = rt.Trace(
        spans=spans, modules={0: programs}, blocks={}, threads=[spans],
        op_self_s={0: {"conditional.1": 0.0002,
                       "conditional.1/fusion.8": 0.0030,
                       "conditional.1/fusion.9": 0.0010,
                       "while.9/fusion.1": 0.0020}})
    return Results(
        window_s=10.0, series={}, scalars={}, counters={},
        program_series={"serve_burst_depth": [1.0, 3.0, 9.0, 4.0, 4.0],
                        "serve_absorb_commit_bytes": [0.0, 0.0, 774144.0,
                                                      0.0, 258048.0]},
        trace=trace, trace_window=rt.window_of(trace))


def test_the_new_metric_files_load_and_read(monkeypatch):
    with open(os.path.join(run.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    # By name, and a list by its head: a later PR appends entries and cells.
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, cells in NEW_METRICS.items():
        assert entries[name]["workloads"][:len(cells)] == cells
        assert entries[name]["moves"] == "match_frames_per_s"
        assert entries[name]["layer"] == "device programs"
    ops = {"conditional.1": ("absorb",),
           "fusion.8": ("absorb", "commit", "ring_read"),
           "fusion.9": ("absorb", "commit", "ring_write", "row_layout"),
           "fusion.1": ("burst", "ring_write")}
    monkeypatch.setattr(xla_cache, "_EXEC_PHASES", {
        "batched_tick_S64_B8_F8": {"ops": ops, "inherited": 0,
                                   "unscoped": 0}})
    results = _traced(ops)
    # The commit's two fusions over two dispatches; not the conditional's
    # own time, and not the burst's write.
    assert _read("scope_commit_ms.serve", results) == pytest.approx(2.0)
    assert _read("burst_depth.p50.serve", results) == 4.0
    assert _read("absorb_commit_bytes.serve", results) == pytest.approx(
        4 * 258048 / 5)
    # A program without the series and without a phase map (a commit before
    # PR 50): nothing, and nothing raised. One with a map but no such scope
    # (the parent of PR 52) reads no time under it.
    results.program_series.clear()
    monkeypatch.setattr(xla_cache, "_EXEC_PHASES", {})
    for name in NEW_METRICS:
        assert _read(name, results) is None
    assert run.read_metrics(
        [{"name": n, "unit": "x"} for n in NEW_METRICS], "layer_metrics",
        results) == {}
    monkeypatch.setattr(xla_cache, "_EXEC_PHASES", {
        "batched_tick_S64_B8_F8": {
            "ops": {k: tuple(s for s in v if s != "commit")
                    for k, v in ops.items()},
            "inherited": 0, "unscoped": 0}})
    assert _read("scope_commit_ms.serve", results) == 0.0


def test_the_cell_reports_what_the_issue_names():
    with open(os.path.join(run.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])}
    # The lists that accepted tests hold equal (``==``) to fixed cells
    # (``phase_*`` / ``scope_*`` of PR 50, ``absorb_fill_share.serve``,
    # ``live_entities.serve``, ...) are left to the ``benchmark`` PR that
    # loosens them (``PERF.md`` section 7): nothing is asserted of them here.
    for name in ("scope_commit_ms.serve", "burst_depth.p50.serve",
                 "absorb_commit_bytes.serve", "burst_fill_share.serve",
                 "tick_program_ms.serve", "tick_roofline.serve",
                 "group_tick_ms.serve", "session_poll_ms.serve",
                 "withheld_frame_share", "spec_full_hit_share.serve"):
        assert name in mine, name
    moved = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in moved["match_frames_per_s"]["workloads"]

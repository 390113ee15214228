"""What PR 47 adds to the benchmark as code: loop kind
``match_server_p2p_world`` (a served frame that ends when the device has
finished it, the anchored reference on confirmed frames only, its two
guarantee rows) and the two metric files.

The cell itself (``boids256.wan``) is rehearsed end to end, traced and under
both controls by ``test_benchmark_rehearsal.py``, which takes its cases from
``BENCHMARK.json``; the program at the shape is held by
``tests/test_serve_boids_p2p.py``. Here: the reference half of ``check()``
on rings the plain reference itself stepped, whose rows past a match's
confirmed frame hold a misprediction (float32: passes, because those rows
are never read; both controls: fail by the reference rows); the same on a
live driver whose hosts were forced ahead of their far ends; the order of
the window's loop, with recording stand-ins; the metric files.
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
from benchmark.drivers.match_server_p2p_world import Driver, _DrainedFrames
from benchmark.drivers.match_server_world import Driver as WorldDriver
from benchmark.readers.common import Results
from benchmark.reference import boids_np as ref
from benchmark.titles import boids as title

CELL = "boids256.wan"
NEW_METRICS = ["absorb_fill_share.serve", "absorb_depth.p95.serve"]


def _context(control=None, seed=2**31 + 47, trace=False):
    _, _, config, traffic = run.load_cell(CELL, run.load_toy(CELL))
    return Context(config=config, traffic=traffic, seed=seed, trace=trace,
                   control=control, title=title, reference=ref,
                   annotate=lambda name: contextlib.nullcontext())


def test_the_cell_is_the_served_title_behind_the_hosted_network():
    """``boids_1k_server256``'s sizes and limits with
    ``box_game_server256_p2p``'s sessions, under the accepted mix."""
    manifest, cell, config, traffic = run.load_cell(CELL)
    world = run.load_cell("boids256.synctest")[2]
    hosted = run.load_cell("server256.wan")[2]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "boids_1k_server256_p2p", "wan_bursts", 1)
    assert config["driver"] == "match_server_p2p_world"
    s = config["settings"]
    for key in ("num_players", "num_entities", "max_prediction", "fps",
                "input_delay", "capacity", "stagger_groups",
                "speculation_branches", "speculation_frames", "force_kernel"):
        assert s[key] == world["settings"][key], key
    for key, value in hosted["settings"].items():
        assert s[key] == value, key
    assert {k: v["limit"] for k, v in config["limits"].items()} == {
        k: v["limit"] for k, v in world["limits"].items()}
    assert config["undecided_margin"] == world["undecided_margin"]
    assert config["undecided_clamp_gain"] == world["undecided_clamp_gain"]
    assert set(config["reduced"]) == {"far_end_session_only",
                                      "device_drained_each_frame"}
    assert traffic == run.load_cell("server256.wan")[3]
    assert config["architecture"] is None


# ---------------------------------------------------------------------------
# The anchored reference on confirmed frames only, on rings the reference
# stepped
# ---------------------------------------------------------------------------

N, DEPTH, FRAMES, MATCHES, AHEAD = 64, 9, 14, 4, 3


def _stepped_rings(table, control):
    """``[MATCHES, DEPTH]`` ring rows of ``FRAMES`` frames a match, stepped
    by the plain reference under ``control``; row ``f % DEPTH`` holds frame
    f. The last ``AHEAD`` frames stand for a host ahead of its far end:
    their rows were stepped through a mispredicted remote input."""
    pos, vel = ref.spawn(MATCHES, 2, N)
    ring_p = np.zeros((MATCHES, DEPTH, N, 2), np.float32)
    ring_v = np.zeros_like(ring_p)
    frames = np.full((MATCHES, DEPTH), -1, np.int32)
    spawn_p, spawn_v = ref.spawn(1, 2, 2)
    for f in range(FRAMES):
        ring_p[:, f % DEPTH], ring_v[:, f % DEPTH] = pos, vel
        frames[:, f % DEPTH] = f
        bits = table[:, :, f].copy()
        if f >= FRAMES - 1 - AHEAD:
            bits[:, 1] = 10 - bits[:, 1]    # the remote input, mispredicted
        pos, vel = ref.step(pos, vel, bits,
                            "bfloat16" if control == "bf16_state" else
                            "float32")
        if control == "freeze_last_player":
            pos[:, 1], vel[:, 1] = spawn_p[0, 1], spawn_v[0, 1]
    return ring_p, ring_v, frames


def _driver_on_stepped_rings(control):
    driver = Driver(_context(control))
    table = np.random.RandomState(3).choice(
        np.asarray([0, 2, 8, 10], np.uint8), size=(MATCHES, 2, FRAMES + 1))
    driver.keys = types.SimpleNamespace(table=lambda horizon: table)
    ring_p, ring_v, frames = _stepped_rings(table, control)
    count = np.where(frames >= 0, frames, 0).astype(np.uint32)
    groups = []
    for g in range(2):      # matches 0-1 in group 0, 2-3 in group 1
        rows = slice(2 * g, 2 * g + 2)
        groups.append(types.SimpleNamespace(
            slots=[types.SimpleNamespace(frame=FRAMES)] * 2,
            rings=types.SimpleNamespace(
                frames=frames[rows],
                states=types.SimpleNamespace(
                    components={"position": ring_p[rows],
                                "velocity": ring_v[rows]},
                    resources={"frame_count": count[rows]}))))
    driver.server = types.SimpleNamespace(groups=groups)
    driver.live = {k: types.SimpleNamespace(group=k // 2, slot=k % 2)
                   for k in range(MATCHES)}
    # A host at frame FRAMES whose far end confirmed all but the last
    # AHEAD + 1 inputs: the snapshot of frame confirmed + 1 is the newest
    # that rests on confirmed inputs.
    confirmed = FRAMES - 2 - AHEAD
    driver.hosts = [types.SimpleNamespace(
        current_frame=FRAMES, confirmed_frame=lambda: confirmed)] * MATCHES
    driver.sample = [1]
    return driver, frames, confirmed


@pytest.mark.parametrize("control,failed", [
    (None, set()),
    ("bf16_state", {"reference.translation_gap", "reference.velocity_gap"}),
    ("freeze_last_player", {"reference.translation_gap",
                            "reference.velocity_gap"}),
])
def test_anchored_rows_on_confirmed_frames_see_both_controls(control, failed):
    driver, frames, confirmed = _driver_on_stepped_rings(control)
    view = driver._confirmed_view()
    # The view hides what rests on a prediction and nothing else; the rings
    # themselves are left as they were.
    for g, group in enumerate(view.server.groups):
        real = frames[2 * g:2 * g + 2]
        assert np.array_equal(
            group.rings.frames, np.where(real > confirmed + 1, -1, real))
        assert group.rings.frames.max() == confirmed + 1 < real.max()
    rows = WorldDriver._anchored(view)
    assert [c.name for c in rows] == [
        "reference.frame_count_gap", "reference.translation_gap",
        "reference.velocity_gap", "reference.undecided_share"]
    assert {c.name for c in rows if not c.ok} == failed
    # Three matches' newest confirmed step and every held confirmed step of
    # the sampled one: frames FRAMES - DEPTH .. confirmed + 1.
    held = confirmed + 1 - (FRAMES - DEPTH)
    assert driver.scalars["anchored_steps"] == 3 + held
    assert driver.scalars["anchored_worst"]["frame"] <= confirmed
    # The same rings with nothing hidden: the mispredicted steps are taken
    # and fail the sound run too, which is what the view is for.
    rows = WorldDriver._anchored(driver)
    assert not all(c.ok for c in rows)

    # A match whose confirmed frame left its ring holds no such step.
    driver.hosts = list(driver.hosts)
    driver.hosts[2] = types.SimpleNamespace(
        current_frame=FRAMES, confirmed_frame=lambda: FRAMES - DEPTH - 1)
    rows = WorldDriver._anchored(driver._confirmed_view())
    assert [(c.name, c.value, c.ok) for c in rows] == [
        ("reference.no_step_held", 1.0, False)]


# ---------------------------------------------------------------------------
# A live driver at the toy size
# ---------------------------------------------------------------------------


class _Ticks:
    """``time`` for the inherited window loop: a millisecond a reading."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self) -> float:
        self.now += 1e-3
        return self.now


@pytest.fixture(scope="module")
def hosted():
    """The cell's driver at its toy size, set up (every session RUNNING,
    warm-up served) and not yet measured."""
    d = Driver(_context(trace=True))
    d.setup()
    return d


def test_setup_binds_the_title_and_meets_a_hit_before_the_window(hosted):
    d = hosted
    assert d.initial.components["position"].shape == (64, 2)
    assert len(d.live) == len(d.hosts) == len(d.far) == 8
    assert sum(f.runner is not None for f in d.far) == len(d.sample) == 2
    assert d.drained and d.scalars["serve_carry_bytes"] > 0
    # What ``guarantee.no_speculation_hit`` asks, met by the toy's warm-up.
    assert d._total("spec_hits") > 0 and d._total("absorb_steps_total") > 0
    shapes = d.cost_shapes()
    assert (shapes["num_slots"], shapes["num_entities"]) == (4, 64)
    assert shapes["slot_rings_bytes"] > shapes["slot_states_bytes"] > 0


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
    # The clock stops, the far ends tick, the frame is served and the device
    # waited for: in that order, every frame; then the final wait.
    assert events == ["far_ends", "frame", "block"] * served + ["block"]
    assert d.busy_stops == 0 and d.drained
    assert len(d.series["device_drain_ms"]) == served
    assert len(d.series["serve_frame_ms"]) == served
    assert d.counters()["absorb_step_slots_total"] >= 0

    # A loop whose wait does not complete is counted, stop by stop.
    events.clear()
    monkeypatch.setattr(d, "_block", lambda: events.append("no block"))
    d.window(0.05)
    assert d.busy_stops == d.scalars["frames_served"] - 1 > 0
    monkeypatch.undo()
    d._block()


def test_no_step_past_the_confirmed_frame_with_hosts_forced_ahead(
        hosted, monkeypatch):
    d = hosted
    for _ in range(5):          # served frames the far ends do not answer
        d.net.advance(d.dt)
        d.server.run_frame()
    d._block()
    upto = d._confirmed_upto()
    lead = [d.hosts[k].current_frame - 1 - upto[k] for k in d.live]
    assert max(lead) >= 5
    view = d._confirmed_view()
    hidden = 0
    for k, h in d.live.items():
        real = np.asarray(d.server.groups[h.group].rings.frames)[h.slot]
        seen = view.server.groups[h.group].rings.frames[h.slot]
        assert real.max() == d.hosts[k].current_frame - 1
        assert seen.max() <= upto[k]
        assert np.array_equal(seen, np.where(real > upto[k], -1, real))
        hidden += int((seen != real).sum())
    assert hidden >= 5 * len(d.live) // 2
    rows = WorldDriver._anchored(view)
    assert [c.name for c in rows if not c.ok] == []
    worst = d.scalars["anchored_worst"]
    assert worst["frame"] + 1 <= upto[worst["match"]]
    # The far ends catch up, and a window's whole check passes.
    monkeypatch.setattr(match_server_p2p, "time", _Ticks())
    d.window(0.05)
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


def test_the_new_metric_files_load_and_read():
    with open(os.path.join(run.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-2:]] == NEW_METRICS
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "match_frames_per_s"
    assert entries["absorb_fill_share.serve"]["layer"] == "device programs"
    assert entries["absorb_depth.p95.serve"]["layer"] == "speculation"
    results = Results(
        window_s=1.0, series={}, scalars={},
        counters={"absorb_steps_total": 24, "absorb_step_slots_total": 640},
        program_series={"serve_absorb_depth": [0.0] * 90 + [3.0] * 6
                        + [8.0] * 4})
    # 24 committed frames over 64 slots x 10 copy steps.
    assert _read("absorb_fill_share.serve", results) == pytest.approx(3.75)
    assert _read("absorb_depth.p95.serve", results) == 3.0
    # A window in which nobody committed; the parent's program, which
    # observes no ``serve_absorb_depth``: nothing, and nothing raised.
    results.counters.update(absorb_steps_total=0, absorb_step_slots_total=0)
    results.program_series.clear()
    for name in NEW_METRICS:
        assert _read(name, results) is None
    assert run.read_metrics(
        [{"name": n, "unit": "x"} for n in NEW_METRICS], "layer_metrics",
        results) == {}

"""What PR 37 adds to the benchmark as code: loop kind ``match_server_world``
(its title binding and the anchored reference half of ``check()``), the
served force's operation count, and the four metric files.

The cell itself (``boids256.synctest``) is rehearsed end to end, traced and
under both controls by ``test_benchmark_rehearsal.py``, which takes its
cases from ``BENCHMARK.json``; here the reference half of ``check()`` is
held to both controls directly, on rings the plain reference itself stepped
(float32: passes; state rounded through bfloat16, or the last leader put
back on its spawn: fails by the reference rows), with no device program.
"""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import run
from benchmark.costs import pairwise_force_served
from benchmark.drivers.common import Context
from benchmark.drivers.match_server_world import Driver, _Sized
from benchmark.readers.common import Results
from benchmark.reference import boids_np as ref
from benchmark.titles import boids as title

CELL = "boids256.synctest"
NEW_METRICS = ["pairwise_kernel_ms.serve", "pairwise_roofline.serve",
               "carry_bytes.serve", "tick_stage_bytes.serve"]


def _context(control=None):
    _, _, config, traffic = run.load_cell(CELL, run.load_toy(CELL))
    return Context(config=config, traffic=traffic, seed=5, trace=False,
                   control=control, title=title, annotate=None, reference=ref)


def test_the_title_is_bound_to_the_configurations_settings():
    sized = _Sized(title, {"num_entities": 48, "force_kernel": "xla"})
    world = sized.make_world(2)
    assert world.components["position"].shape == (48, 2)
    assert len(sized.make_schedule().systems) == 2
    assert len(sized.make_schedule("bf16_state").systems) == 3
    assert sized.CONTROLS is title.CONTROLS and sized.REFERENCE == "boids_np"


def test_served_force_flops_against_a_hand_count():
    shapes = {"num_entities": 1024, "num_slots": 64,
              "speculation_branches": 8, "speculation_frames": 8,
              "live_frames": 3}
    # 31 flops x 1,048,576 pairs x 64 slots x (64 rollout + 3 live) frames.
    assert pairwise_force_served.flops(shapes) == 31 * 1048576 * 64 * 67
    assert pairwise_force_served.flops(shapes) == 139385110528.0
    # Under the v5e's bf16 peak even if the kernel took no longer than the
    # client's rate allows (1.5e11 pairs/s: PERF.md section 5).
    assert pairwise_force_served.flops(shapes) / 197e12 < 0.030


def test_the_new_metric_files_load_and_read():
    with open(os.path.join(run.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    results = Results(
        window_s=1.0, series={}, scalars={"serve_carry_bytes": 150e6},
        counters={}, program_series={"tick_stage_bytes": [9.0, 11.0, 10.0]},
        peaks={"bf16_flops_per_s": 197e12},
        cost_shapes={"num_entities": 1024, "num_slots": 64,
                     "speculation_branches": 8, "speculation_frames": 8,
                     "live_frames": 3},
        values={"pairwise_kernel_ms.serve": 33.0})
    got = {}
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "match_frames_per_s"
        with open(os.path.join(run.HERE, "layer_metrics", name + ".json"),
                  encoding="utf-8") as f:
            spec = json.load(f)
        reader = importlib.import_module(f"benchmark.readers.{spec['kind']}")
        got[name] = reader.read(spec, results)
    assert got["carry_bytes.serve"] == 150e6
    assert got["tick_stage_bytes.serve"] == 10.0
    assert got["pairwise_kernel_ms.serve"] is None      # no trace: nothing
    # 1.3939e11 flops / 197e12 / 33 ms = 2.14 %.
    assert got["pairwise_roofline.serve"] == pytest.approx(2.1441, rel=1e-3)
    # A program without the series (the parent) reports nothing.
    results.scalars.pop("serve_carry_bytes")
    results.program_series.clear()
    for name in ("carry_bytes.serve", "tick_stage_bytes.serve"):
        with open(os.path.join(run.HERE, "layer_metrics", name + ".json"),
                  encoding="utf-8") as f:
            spec = json.load(f)
        reader = importlib.import_module(f"benchmark.readers.{spec['kind']}")
        assert reader.read(spec, results) is None


# ---------------------------------------------------------------------------
# The anchored reference half of check(), on rings the reference stepped
# ---------------------------------------------------------------------------

N, DEPTH, FRAMES, MATCHES = 64, 9, 14, 4


def _stepped_rings(table, control):
    """``[MATCHES, DEPTH]`` ring rows of ``FRAMES`` frames a match, stepped by
    the plain reference under ``control``; row ``f % DEPTH`` holds frame f."""
    pos, vel = ref.spawn(MATCHES, 2, N)
    ring_p = np.zeros((MATCHES, DEPTH, N, 2), np.float32)
    ring_v = np.zeros_like(ring_p)
    frames = np.full((MATCHES, DEPTH), -1, np.int32)
    spawn_p, spawn_v = ref.spawn(1, 2, 2)
    for f in range(FRAMES):
        ring_p[:, f % DEPTH], ring_v[:, f % DEPTH] = pos, vel
        frames[:, f % DEPTH] = f
        pos, vel = ref.step(pos, vel, table[:, :, f],
                            "bfloat16" if control == "bf16_state" else
                            "float32")
        if control == "freeze_last_player":
            pos[:, 1], vel[:, 1] = spawn_p[0, 1], spawn_v[0, 1]
    return ring_p, ring_v, frames


@pytest.mark.parametrize("control,failed", [
    (None, set()),
    ("bf16_state", {"reference.translation_gap", "reference.velocity_gap"}),
    ("freeze_last_player", {"reference.translation_gap",
                            "reference.velocity_gap"}),
])
def test_anchored_reference_rows_see_both_controls(control, failed):
    driver = Driver(_context(control))
    driver.keys = types.SimpleNamespace(table=lambda horizon: table)
    table = np.random.RandomState(3).choice(
        np.asarray([0, 1, 2, 4, 5, 6, 8, 9, 10], np.uint8),
        size=(MATCHES, 2, FRAMES + 1))
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
    driver.sample = [1]
    rows = driver._anchored()
    assert [c.name for c in rows] == [
        "reference.frame_count_gap", "reference.translation_gap",
        "reference.velocity_gap", "reference.undecided_share"]
    # Three matches' newest step and all 8 held steps of the sampled one.
    assert driver.scalars["anchored_steps"] == 3 + (DEPTH - 1)
    assert driver.scalars["anchored_matches"] == MATCHES
    assert {c.name for c in rows if not c.ok} == failed
    assert rows[0].value == 0

    # A match none of whose steps is held is a failure with a name.
    groups[1].rings.frames[1, :] = -1
    rows = driver._anchored()
    assert [(c.name, c.value, c.ok) for c in rows] == [
        ("reference.no_step_held", 1.0, False)]

"""What PR 44 adds to the benchmark as code: the title glue of ``particles``,
its plain reference, loop kind ``match_server_churn`` (the comparison by
rollback id) and the two metric files.

The cell itself (``particles.synctest``) is rehearsed end to end, traced and
under both controls by ``test_benchmark_rehearsal.py``, which takes its
cases from ``BENCHMARK.json``; here the reference half of ``check()`` is
held to both controls and to every way a lifecycle can go wrong directly,
on worlds the plain reference itself stepped and laid out in rows in another
order (identity is the id, not the row), with no device program.
"""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import run
from benchmark.drivers.common import Context
from benchmark.drivers.match_server_churn import Driver
from benchmark.readers.common import Results
from benchmark.reference import particles_np as ref
from benchmark.titles import particles as title

CELL = "particles.synctest"
NEW_METRICS = ["live_entities.serve", "entity_births.serve"]
MASKS = np.asarray([0, 1, 2, 4, 5, 6, 8, 9, 10], np.uint8)


def _context(control=None):
    _, _, config, traffic = run.load_cell(CELL, run.load_toy(CELL))
    return Context(config=config, traffic=traffic, seed=5, trace=False,
                   control=control, title=title, annotate=None, reference=ref)


# ---------------------------------------------------------------------------
# The title glue and the reference
# ---------------------------------------------------------------------------


def test_the_title_is_bound_to_the_configurations_settings():
    sized = title.configured({"rate": 6, "world_capacity": 640})
    world = sized.make_world(2)
    assert world.components["position"].shape == (640, 2)
    assert world.resources["emitter_position"].shape == (2, 2)
    assert not np.asarray(world.alive).any()
    assert len(sized.make_schedule().systems) == 5
    for control in title.CONTROLS:
        systems = sized.make_schedule(control).systems
        assert len(systems) == 6 and systems[4] is title.CONTROLS[control]
    assert sized.REFERENCE == "particles_np"
    other = sized.for_match(world, 0xDEADBEEF)
    assert int(other.resources["match_seed"]) == 0xDEADBEEF
    assert other.components["position"] is world.components["position"]
    pos, vel, count, by_name = sized.readback(world, 2)
    assert pos.shape == vel.shape == (640, 2) and int(count) == 0
    assert set(by_name) == {"alive", "id", "ttl", "position", "velocity",
                            "next_id", "fizzled", "frame_count", "emitter"}


def test_a_program_without_the_title_is_refused_by_name(monkeypatch):
    import sys

    from bevy_ggrs_tpu import models

    monkeypatch.delattr(models, "particles")
    monkeypatch.setitem(sys.modules, "bevy_ggrs_tpu.models.particles", None)
    with pytest.raises(SystemExit) as err:
        title.configured({"rate": 4, "world_capacity": 384})
    assert "no title 'particles'" in str(err.value)


def test_the_references_hash_is_the_programs_bit_for_bit():
    import jax.numpy as jnp

    from bevy_ggrs_tpu.models import particles
    from bevy_ggrs_tpu.state import DEVICE_ID_BASE

    assert ref.ID_BASE == DEVICE_ID_BASE
    ids = np.arange(ref.ID_BASE, ref.ID_BASE + 8192, dtype=np.int32)
    for seed in (0, 1, 0xFFFFFFFF, 2511000401):
        ttl, vel = particles.draws(jnp.uint32(seed), jnp.asarray(ids))
        want_ttl, want_vel = ref.draws(np.uint32(seed), ids)
        assert np.array_equal(np.asarray(ttl), want_ttl)
        assert np.array_equal(np.asarray(vel), want_vel)
        assert want_ttl.min() == ref.TTL_MIN and want_ttl.max() == ref.TTL_MAX
        assert -ref.SPEED <= want_vel.min() and want_vel.max() < ref.SPEED
        assert abs(want_ttl.mean() - 74.5) < 0.5
    assert np.array_equal(ref.emitter_spawn(2), particles.emitter_spawn(2))
    for name in ("RATE", "CAPACITY", "TTL_MIN", "TTL_SPAN", "DT", "SPEED",
                 "EMITTER_SPEED", "ARENA_HALF"):
        assert getattr(ref, name) == getattr(particles, name), name
    assert np.array_equal(ref.GRAVITY_DT, particles.GRAVITY_DT)


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__, encoding="utf-8") as f:
        src = f.read()
    assert "import jax" not in src and "from jax" not in src
    assert "import bevy_ggrs_tpu" not in src and "from bevy" not in src


def test_the_reference_keeps_each_match_at_its_own_frame():
    bits = np.random.RandomState(2).choice(MASKS, size=(3, 2, 120))
    lengths = np.asarray([120, 37, 0])
    world = ref.replay_worlds(bits, lengths, [7, 8, 9], rate=5, capacity=512)
    assert world["frame_count"].tolist() == [120, 37, 0]
    assert world["next_id"].tolist() == [ref.ID_BASE + 5 * n for n in lengths]
    alone = ref.replay_worlds(bits[1:2], [37], [8], rate=5, capacity=512)
    for got, want in zip(ref.by_id(world, 1), ref.by_id(alone, 0)):
        assert np.array_equal(got, want)
    assert not world["alive"][2].any()
    # 5 x (74.5 - 1) live at a frame's end once the first have died.
    assert abs(world["alive"][0].sum() - 5 * 73.5) < 30


# ---------------------------------------------------------------------------
# The comparison by id, on worlds the reference stepped
# ---------------------------------------------------------------------------

MATCHES, FRAMES = 4, 110


def _program_side(table, seeds, control, rate, capacity):
    """What the program's states would hold: the reference's own worlds
    (stepped under ``control``), each laid out in ``capacity`` rows in an
    order of its own, dead rows filled with junk."""
    worlds = ref.spawn(seeds, 2, rate)
    home = ref.emitter_spawn(2)
    for f in range(FRAMES):
        worlds = ref.step(worlds, table[:, :, f],
                          "bfloat16" if control == "bf16_state" else
                          "float32", capacity)
        if control == "freeze_last_player":
            worlds["emitter"][:, 1] = home[1]
    rng = np.random.RandomState(4)
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
        out[name] = worlds[name].copy()
    return out


def _driver(control, spoil=None):
    driver = Driver(_context(control))
    s = driver.ctx.config["settings"]
    rate, capacity = int(s["rate"]), int(s["world_capacity"])
    table = np.random.RandomState(3).choice(MASKS, size=(MATCHES, 2, FRAMES))
    driver.keys = types.SimpleNamespace(table=lambda horizon: table)
    driver.seeds = np.asarray([11, 22, 33, 44], np.uint32)
    mine = _program_side(table, driver.seeds, control, rate, capacity)
    if spoil is not None:
        spoil(mine)
    groups = []
    for g in range(2):      # matches 0-1 in group 0, 2-3 in group 1
        rows = slice(2 * g, 2 * g + 2)
        groups.append(types.SimpleNamespace(
            slots=[types.SimpleNamespace(frame=FRAMES)] * 2,
            states=types.SimpleNamespace(
                alive=mine["alive"][rows], rollback_id=mine["id"][rows],
                components={k: mine[k][rows]
                            for k in ("ttl", "position", "velocity")},
                resources={"next_rollback_id": mine["next_id"][rows],
                           "spawn_fizzled": mine["fizzled"][rows],
                           "frame_count": mine["frame_count"][rows],
                           "emitter_position": mine["emitter"][rows]})))
    driver.server = types.SimpleNamespace(groups=groups)
    driver.live = {k: types.SimpleNamespace(group=k // 2, slot=k % 2)
                   for k in range(MATCHES)}
    return driver, rate


def _first_live(mine, match=2):
    return int(np.flatnonzero(mine["alive"][match])[0])


def _lose_a_particle(mine):
    mine["alive"][2, _first_live(mine)] = False


def _age_a_particle(mine):
    mine["ttl"][2, _first_live(mine)] += 1


def _mint_an_id_twice(mine):
    a, b = np.flatnonzero(mine["alive"][2])[:2]
    mine["id"][2, b] = mine["id"][2, a]


def _skip_an_id(mine):
    mine["next_id"][1] += 1


def _fizzle(mine):
    mine["fizzled"][3] += 2


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
def test_the_comparison_by_id_sees_both_controls_and_every_lifecycle_fault(
        control, spoil, failed):
    driver, rate = _driver(control, spoil)
    rows = driver._by_id()
    assert [c.name for c in rows] == [
        "guarantee.spawn_fizzled", "guarantee.duplicate_live_ids",
        "reference.lifecycle_gap", "reference.frame_count_gap",
        "reference.translation_gap", "reference.velocity_gap"]
    assert {c.name for c in rows if not c.ok} == failed
    assert driver.scalars["checked_matches"] == MATCHES
    assert driver.scalars["checked_frames_each"] == [FRAMES, FRAMES]
    assert abs(driver.scalars["live_entities"] - rate * 73.5) < rate * 8
    if control is None and spoil is None:
        # The reference against itself, rows shuffled: nothing differs.
        assert all(c.value == 0 for c in rows)


def test_the_lowering_counts_become_scalars():
    driver = Driver(_context())
    driver.program_metrics = types.SimpleNamespace(
        series={"serve_carry_bytes": [5.0, 5.0]},
        counters={'served_frames': 7,
                  'ring_row_lowering{kind="shaped"}': 26})
    driver._lowering_scalars()
    assert driver.scalars["serve_carry_bytes"] == 5.0
    assert driver.scalars["ring_row_lowering"] == {
        'ring_row_lowering{kind="shaped"}': 26}
    # A program that observed nothing says nothing.
    driver = Driver(_context())
    driver.program_metrics = types.SimpleNamespace(series={}, counters={})
    driver._lowering_scalars()
    assert "serve_carry_bytes" not in driver.scalars
    assert driver.scalars["ring_row_lowering"] == {}


def test_the_new_metric_files_load_and_read():
    with open(os.path.join(run.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    results = Results(
        window_s=1.0, series={}, counters={}, program_series={},
        scalars={"live_entities": 7351.5, "entity_births": 100.0})
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "match_frames_per_s"
        assert entries[name]["layer"] == "device programs"
        with open(os.path.join(run.HERE, "layer_metrics", name + ".json"),
                  encoding="utf-8") as f:
            spec = json.load(f)
        reader = importlib.import_module(f"benchmark.readers.{spec['kind']}")
        assert reader.read(spec, results) == results.scalars[spec["key"]]
        # A program that says nothing (the parent) reports nothing.
        assert reader.read(spec, Results(
            window_s=1.0, series={}, counters={}, program_series={},
            scalars={})) is None
    # The cell reports what the served SyncTest cells report, and not the
    # metrics whose lists accepted tests pin.
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])}
    boids = {m["name"] for m in manifest["per_layer"]
             if "boids256.synctest" in m.get("workloads", [])}
    assert mine - boids == set(NEW_METRICS)
    # (the force kernel's two are boids'; the other two are pinned to its
    # cell by tests/benchmark/test_benchmark_served_world.py)
    assert boids - mine == {"pairwise_kernel_ms.serve",
                            "pairwise_roofline.serve", "carry_bytes.serve",
                            "tick_stage_bytes.serve"}
    assert not mine & {"launch_lag_ms.serve", "call_tail_ms.serve",
                       "dispatch_rounds.serve"}

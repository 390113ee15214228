"""What PR 31 adds to the benchmark: the anchored reference check of loop
kind ``p2p_pair_world`` (on a ring stepped here, without the loop: the
rehearsal runs the whole cell, its controls included), the reader of an
operation's device time, and the force's operations from shapes."""

import json
import os
import types

import numpy as np
import pytest

from benchmark.costs import pairwise_force
from benchmark.drivers.p2p_pair_world import Driver
from benchmark.readers import trace_bytes, trace_op
from benchmark.readers.common import Results
from benchmark.reduce import trace as rt
from benchmark.reference import boids_np as ref
from benchmark.titles import boids as title

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BOIDS, PLAYERS, DEPTH, WINDOW = 64, 2, 10, 8


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json"), encoding="utf-8") as f:
        return json.load(f)


# -- the anchored check -------------------------------------------------------


def _ring(control=None, frames=24, edit=None):
    """A ring as peer 0's holds it after ``frames`` confirmed frames: slot
    ``f % DEPTH`` holds the state at frame ``f``; and the inputs that led
    there. ``edit(f, state)`` may change a state before it is stepped."""
    import jax

    from bevy_ggrs_tpu.schedule import make_inputs

    step = jax.jit(title.make_schedule(control, "xla"))
    rng = np.random.RandomState(3)
    table = rng.choice(np.asarray([0, 1, 2, 4, 5, 6, 8, 9, 10], np.uint8),
                       size=(1, PLAYERS, frames + 1))
    state = title.make_world(PLAYERS, BOIDS)
    slots = [state] * DEPTH                 # an empty slot holds anything
    held = np.full((DEPTH,), -1, np.int32)
    for f in range(frames + 1):
        if edit is not None:
            state = edit(f, state)
        slots[f % DEPTH], held[f % DEPTH] = state, f
        state = step(state, make_inputs(table[0, :, f]))
    states = jax.tree_util.tree_map(lambda *x: np.stack(x), *slots)
    return types.SimpleNamespace(states=states, frames=held), table


def _driver(ring, table, limits=None):
    limits = limits or {"reference.translation_gap": 1e-5,
                        "reference.velocity_gap": 1e-6,
                        "reference.undecided_share": 0.05}
    driver = object.__new__(Driver)
    driver.plain_reference = ref
    driver.ctx = types.SimpleNamespace(
        title=title, config={"limits": {k: {"limit": v}
                                        for k, v in limits.items()}})
    driver.runner = types.SimpleNamespace(ring=ring)
    driver.keys = types.SimpleNamespace(table=lambda upto: table)
    driver.players, driver.window_frames = PLAYERS, WINDOW
    driver.margin, driver.scalars = 1e-5, {}
    return driver


def _rows(driver, upto):
    return {c.name: c for c in driver._anchored(upto)}


def test_anchored_check_passes_in_float32_over_every_held_step():
    driver = _driver(*_ring())
    rows = _rows(driver, 24)
    assert list(rows) == ["reference.frame_count_gap",
                          "reference.translation_gap",
                          "reference.velocity_gap",
                          "reference.undecided_share"]
    assert all(c.ok for c in rows.values()), rows
    assert rows["reference.frame_count_gap"].value == 0
    assert 0 < rows["reference.translation_gap"].value < 2e-6
    assert driver.scalars["anchored_steps"] == WINDOW
    # Near the head of a match fewer steps are held: all of them are taken.
    early = _driver(*_ring(frames=3))
    assert all(c.ok for c in _rows(early, 3).values())
    assert early.scalars["anchored_steps"] == 3


@pytest.mark.parametrize("control,fails", [
    ("bf16_state", {"reference.translation_gap", "reference.velocity_gap"}),
    ("freeze_last_player", {"reference.translation_gap",
                            "reference.velocity_gap"}),
])
def test_anchored_check_fails_the_controls_by_the_reference(control, fails):
    rows = _rows(_driver(*_ring(control)), 24)
    assert {n for n, c in rows.items() if not c.ok} == fails
    assert rows["reference.frame_count_gap"].value == 0
    # Each step starts from the control's own state, so what is seen is one
    # step's fault: a rounding of a position (2**-9 of 1..2 and up), or the
    # frozen leader's frame of travel (at least the least speed).
    assert rows["reference.translation_gap"].value > 1e-3


def test_an_undecided_boid_is_counted_and_left_out():
    def on_the_radius(f, state):
        if f != 24 - WINDOW:        # the oldest held frame: only ever a start
            return state
        pos = np.array(state.components["position"])
        pos[9] = pos[5] + np.asarray([ref.NEIGHBOR_RADIUS, 0.0], np.float32)
        return state.replace(components={**state.components,
                                         "position": pos})

    base = _rows(_driver(*_ring()), 24)["reference.undecided_share"].value
    driver = _driver(*_ring(edit=on_the_radius))
    rows = _rows(driver, 24)
    assert all(c.ok for c in rows.values()), rows
    share = rows["reference.undecided_share"].value
    assert share >= 2 / (WINDOW * BOIDS) and share > base
    # With no room for it the same run is not correct: counted, not hidden.
    tight = _driver(driver.runner.ring, driver.keys.table(0),
                    {"reference.translation_gap": 1e-5,
                     "reference.velocity_gap": 1e-6,
                     "reference.undecided_share": 1e-4})
    assert not _rows(tight, 24)["reference.undecided_share"].ok


def test_a_ring_that_lost_its_steps_is_not_correct():
    ring, table = _ring()
    ring.frames[:] = -1
    ring.frames[24 % DEPTH] = 24
    rows = _rows(_driver(ring, table), 24)
    assert list(rows) == ["reference.no_step_held"]
    assert not rows["reference.no_step_held"].ok


def test_driver_hands_the_title_its_settings_and_states_its_shapes():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "boids_1k_client.json"), encoding="utf-8") as f:
        config = json.load(f)
    bound = title.configured(config["settings"])
    assert (bound.num_entities, bound.force_kernel) == (
        1024, config["settings"]["force_kernel"])
    assert bound.REFERENCE == "boids_np" and set(bound.CONTROLS) == {
        "bf16_state", "freeze_last_player"}
    driver = object.__new__(Driver)
    driver.ctx = types.SimpleNamespace(config=config)
    shapes = driver.cost_shapes()
    assert shapes == {"num_entities": 1024, "speculation_branches": 128,
                      "speculation_frames": 8, "live_frames": 1}
    assert pairwise_force.flops(shapes) == 31 * 1024 * 1024 * (128 * 8 + 1)


# -- the reader of an operation's device time ---------------------------------


def _trace(ops):
    spans = [(rt.WINDOW_SPAN, 0.0, 1.0)]
    modules = []
    for k in range(4):                  # four ticks: peer 0's, then the far end's
        t = 0.1 + 0.2 * k
        spans += [("bench/update", t, t + 0.05),
                  ("bench/far_end", t + 0.1, t + 0.15)]
        modules += [("jit__unknown(1)", t + 0.01, t + 0.04),
                    ("jit__unknown(2)", t + 0.11, t + 0.12)]
    return rt.Trace(spans=sorted(spans, key=lambda e: e[1]),
                    modules={0: modules}, blocks={}, op_self_s={0: ops})


def _results(trace):
    return Results(window_s=1.0, series={}, scalars={}, counters={},
                   program_series={}, trace=trace,
                   trace_window=rt.window_of(trace) if trace else None,
                   peaks={"bf16_flops_per_s": 197e12},
                   cost_shapes={"num_entities": 1024,
                                "speculation_branches": 128,
                                "speculation_frames": 8, "live_frames": 1})


def test_trace_op_sums_the_force_calls_per_tick_of_peer_0():
    spec = _spec("pairwise_kernel_ms.client")
    results = _results(_trace({
        "while.7/pairwise_force.6": 0.020,     # the rollout's calls
        "while.3/pairwise_force.12": 0.003,    # the burst's
        "pairwise_force.2": 0.001,             # the far end's serial step
        "while.7/fusion.41": 0.5,
        "pairwise_force_rows_mxu2.6": 9.0,     # a parent without the scope
        "while.7/pairwise_force_prologue.1": 9.0,
    }))
    value = trace_op.read(spec, results)
    assert value == pytest.approx(1e3 * 0.024 / 4)
    # The share of the peak, through the accepted reader as it stands.
    results.values["pairwise_kernel_ms.client"] = value
    share = trace_bytes.read(_spec("pairwise_roofline.client"), results)
    flops = 31 * 1024 * 1024 * 1025
    assert share == pytest.approx(100 * flops / 197e12 / 0.006)
    assert 0 < share <= 100


def test_trace_op_finds_nothing_on_a_parent_and_does_not_raise():
    spec = _spec("pairwise_kernel_ms.client")
    parent = _results(_trace({"pairwise_force_rows_mxu2.6": 0.02,
                              "while.7/fusion.41": 0.5}))
    assert trace_op.read(spec, parent) is None
    assert trace_bytes.read(_spec("pairwise_roofline.client"), parent) is None
    assert trace_op.read(spec, _results(None)) is None
    # The force ran, but none of peer 0's programs inside the window.
    idle = _trace({"pairwise_force.2": 0.001})
    idle.modules[0] = []
    assert trace_op.read(spec, _results(idle)) is None


def test_a_program_before_the_third_position_term_is_refused_by_name(
        monkeypatch):
    from bevy_ggrs_tpu.ops import pairwise

    settings = {"num_entities": 64, "force_kernel": "mxu"}
    assert title.configured(settings).force_kernel == "mxu"
    monkeypatch.delattr(pairwise, "SEP_ROWS")
    with pytest.raises(SystemExit) as refused:
        title.configured(settings)
    assert "SEP_ROWS" in str(refused.value)
    # The float32 paths need nothing of it.
    assert title.configured(dict(settings, force_kernel="xla"))

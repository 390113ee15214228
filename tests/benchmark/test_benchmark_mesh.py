"""Loop kind ``p2p_mesh``'s own code (PR 33): the delay-shifted input table
against what a session really hands its simulation, and ``check()`` coming
out false for a spectator or a peer that is not the serial replay. The
rehearsal, manifest and reference tests take the cell from ``BENCHMARK.json``
by themselves."""

import contextlib
import importlib

import numpy as np
import pytest

from benchmark import run
from benchmark.drivers import p2p_mesh
from benchmark.drivers.common import Context

CELL = "lobby8.wan"


def _driver(delay=None, seconds=1.0, seed=2**31 + 5):
    toy = run.load_toy(CELL)
    if delay is not None:
        toy["config"]["settings"]["input_delay"] = delay
    _, _, config, traffic = run.load_cell(CELL, toy)
    title = importlib.import_module(f"benchmark.titles.{config['title']}")
    d = p2p_mesh.Driver(Context(
        config=config, traffic=traffic, seed=seed, trace=False, control=None,
        title=title, annotate=lambda name: contextlib.nullcontext(),
        reference=importlib.import_module(
            f"benchmark.reference.{title.REFERENCE}")))
    d.setup()
    # Peer 1's as-used inputs: the last AdvanceFrame it ran for each frame.
    used = {}
    runner = d.peer1.stage.runner

    def log(requests):
        from bevy_ggrs_tpu.session.requests import (
            AdvanceFrame, LoadGameState, SaveGameState,
        )

        at = runner.frame
        for r in requests:
            if isinstance(r, (LoadGameState, SaveGameState)):
                at = r.frame
            elif isinstance(r, AdvanceFrame):
                used[at] = np.array(r.bits)
                at += 1

    # Its request lists reach the runner through tick() (the fused tick)
    # or handle_requests() (a plain runner, and the fused tick's fall-back).
    for name in ("tick", "handle_requests"):
        call = getattr(runner, name, None)
        if call is not None:
            setattr(runner, name, lambda requests, *rest, _call=call:
                    (log(requests), _call(requests, *rest))[1])
    d.window(seconds)
    return d, used


@pytest.fixture(scope="module")
def ran():
    return _driver()


@pytest.mark.parametrize("delay", [0, 1, 2, 5])
def test_shifted_table_moves_every_stream_by_the_delay(delay):
    table = np.arange(1, 25, dtype=np.uint8).reshape(3, 8)
    got = p2p_mesh.shifted_table(table, delay)
    assert got.shape == table.shape and got.dtype == table.dtype
    assert not got[:, :delay].any()
    assert np.array_equal(got[:, delay:], table[:, :8 - delay])


def test_shifted_table_of_a_delay_past_the_horizon_is_blank():
    table = np.ones((2, 4), np.uint8)
    assert not p2p_mesh.shifted_table(table, 4).any()
    assert not p2p_mesh.shifted_table(table, 9).any()


@pytest.mark.parametrize("delay", [0, 2])
def test_shifted_table_is_what_a_session_hands_its_simulation(delay, ran):
    """Every frame peer 1 simulated for good (all 8 players' inputs
    confirmed there) used the generator's table shifted by the delay; with
    the wrong delay it did not."""
    d, used = ran if delay == 2 else _driver(delay=0, seconds=0.5)
    assert d.delay == delay
    confirmed = d.sessions[1].confirmed_frame()
    frames = [f for f in sorted(used) if f <= confirmed]
    assert len(frames) > 20
    table = p2p_mesh.shifted_table(d.keys.table(confirmed + 1)[0], delay)
    assert all(np.array_equal(used[f], table[:, f]) for f in frames)
    wrong = p2p_mesh.shifted_table(d.keys.table(confirmed + 1)[0], delay + 1)
    assert any(not np.array_equal(used[f], wrong[:, f]) for f in frames)


def test_drain_steps_peer0_even_when_its_stage_is_running_slow():
    """A stage that yields to slower peers (``run_slow``) lets a frame's
    time pass without a step now and then. The drain must still give peer 0
    the step that executes its pending rollback: without it 1 realisation in
    8 compared a snapshot that rested on a misprediction."""
    d, _ = _driver(seconds=0.5, seed=106)
    stage, run_stage = d.a.stage, d.a.stage._run
    forced = []

    def slow_once(app, now):
        if not forced:
            forced.append(d.a.frame)
            stage.run_slow, stage.accumulator = True, 0.0
        return run_stage(app, now)

    stage._run = slow_once
    rows = d.check()
    assert forced and d.a.frame > forced[0]
    assert all(c.ok for c in rows), [c.name for c in rows if not c.ok]


def test_check_holds_and_then_fails_for_a_perturbed_spectator(ran):
    d, _ = ran
    rows = {c.name: c for c in d.check()}
    assert all(c.ok for c in rows.values()), [
        c.name for c in rows.values() if not c.ok]
    assert {"guarantee.spectator_state_differs_from_serial_replay",
            "reference.spectator_translation_gap",
            "reference.spectator_velocity_gap",
            "guarantee.inputs_differ_from_shifted_table"} <= set(rows)
    assert d.scalars["spectator_checked_frames"][0] > 0

    # The spectator's cubes a hair off: its rows fail, peer 0's hold.
    spectator = d.spectator_apps[0].stage.runner
    comps = dict(spectator.state.components)
    comps["translation"] = comps["translation"] + 1e-3
    spectator.state = spectator.state.replace(components=comps)
    # check() moves its oracle forward only: a fresh one for the second go.
    d.oracle = (d.ctx.title.build_plugin(d.players, None)
                .with_input_system(lambda handle, app: 0)
                .with_max_prediction_window(d.window_frames)
                ).build().stage.runner
    again = {c.name: c for c in d.check()}
    failed = {n for n, c in again.items() if not c.ok}
    assert failed == {"guarantee.spectator_state_differs_from_serial_replay",
                      "reference.spectator_translation_gap"}


def test_check_fails_when_the_oracle_is_fed_the_unshifted_table(ran):
    d, _ = ran
    d.oracle = (d.ctx.title.build_plugin(d.players, None)
                .with_input_system(lambda handle, app: 0)
                .with_max_prediction_window(d.window_frames)
                ).build().stage.runner
    d.delay = 0         # the configuration's sessions ran with 2
    rows = {c.name: c for c in d.check()}
    assert rows["guarantee.inputs_differ_from_shifted_table"].value > 0
    assert not rows["guarantee.state_differs_from_serial_replay"].ok
    assert not rows["guarantee.spectator_state_differs_from_serial_replay"].ok


def test_others_times_each_part_and_counts_the_lag_a_tick(ran):
    d, _ = ran
    ticks = d.scalars["ticks"]
    for name in p2p_mesh.OWN_SERIES:
        assert len(d.series[name]) == ticks, name
    parts = np.asarray([d.series[n] for n in p2p_mesh.PARTS[:3]])
    assert np.all(parts.sum(axis=0) <= np.asarray(d.series["others_ms"]) + 1e-6)
    assert len(d.far_ends) == d.players - 2
    assert all(far.runner is None for far in d.far_ends)

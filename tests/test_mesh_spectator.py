"""A lobby on the CPU at toy branches: 8 peers in a full mesh at window 12,
with and without an input delay and a live spectator, through ``GGRSPlugin``
/ ``SessionBuilder`` / ``start_p2p_session`` / ``start_spectator_session``
as the benchmark's loop kind ``p2p_mesh`` wires them (the harness here IS
that driver, built from the configuration's file with two settings
overridden; a count or a correctness fact, never a time).

What the program owes at this shape: zero desyncs with ballots compared,
peer 0 and the spectator bitwise a serial replay of the delay-shifted
inputs and within the plain reference's limits, the two series and the
span PR 33 added, and no executable after warm-up.
"""

import contextlib
import importlib

import numpy as np
import pytest

from benchmark import run
from benchmark.drivers.common import Context

CELL = "lobby8.wan"
CASES = [(2, 1), (0, 1), (2, 0), (0, 0)]      # (input delay, spectators)
ABSORB_BYTES = 5 * 4                           # the absorb program's scalars


class Lobby:
    def __init__(self, delay: int, spectators: int):
        from bevy_ggrs_tpu import spec_runner
        from bevy_ggrs_tpu.utils import xla_cache

        toy = run.load_toy(CELL)
        toy["config"]["settings"].update(input_delay=delay,
                                         spectators=spectators)
        _, _, config, traffic = run.load_cell(CELL, toy)
        title = importlib.import_module(f"benchmark.titles.{config['title']}")
        ctx = Context(
            config=config, traffic=traffic, seed=2**31 + 33 + delay,
            trace=True, control=None, title=title,
            annotate=lambda name: contextlib.nullcontext(),
            reference=importlib.import_module(
                f"benchmark.reference.{title.REFERENCE}"))
        driver_mod = importlib.import_module(
            f"benchmark.drivers.{config['driver']}")
        self.driver = d = driver_mod.Driver(ctx)
        self.staged = []

        xla_cache.install_compile_listeners()
        # One program a tick, as tests/conftest.py pins the suite (this
        # fixture outlives that function-scoped patch).
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(spec_runner, "_blocking_ms",
                          lambda call, reps=3: 0.0)
            d.setup()
            # Peer 0's executor alone: peer 1 has a fused tick of its own.
            stage_args = d.runner._fused._stage_args

            def spy(*args):
                out = stage_args(*args)
                self.staged.append(sum(a.nbytes for a in out))
                return out

            d.runner._fused._stage_args = spy
            series = d.program_metrics.series
            self.base = {k: len(v) for k, v in series.items()}
            self.advances0 = d.sessions[0].current_frame
            self.rollbacks0 = d.runner.rollbacks_total
            self.hits0 = d.runner.spec_hits
            built0 = xla_cache.compile_counters()["backend_compiles"]
            d.window(2.0)
            self.built = (xla_cache.compile_counters()["backend_compiles"]
                          - built0)
            self.advances = d.sessions[0].current_frame - self.advances0
            self.rollbacks = d.runner.rollbacks_total - self.rollbacks0
            self.full_hits = d.runner.spec_hits - self.hits0
            self.window = {k: list(v[self.base.get(k, 0):])
                           for k, v in series.items()}
            self.staged_window = list(self.staged)
            self.rows = {c.name: c for c in d.check()}


@pytest.fixture(scope="module", params=CASES,
                ids=[f"delay{d}-spectators{n}" for d, n in CASES])
def lobby(request):
    return Lobby(*request.param)


def test_no_desync_and_ballots_compared(lobby):
    rows, counts = lobby.rows, lobby.driver.counters()
    assert rows["guarantee.desync_events"].value == 0
    assert rows["guarantee.disconnect_events"].value == 0
    assert counts["checksum_ballots"] > 0
    assert rows["guarantee.no_ballot_compared"].ok
    assert rows["guarantee.a_peer_did_not_advance"].ok
    assert rows["guarantee.speculation_off"].ok
    # A lobby rolls back far more often than a duel: the shape is there.
    assert lobby.rollbacks > 10


def test_peer0_is_the_serial_replay_of_the_delay_shifted_inputs(lobby):
    rows = lobby.rows
    assert rows["guarantee.confirmed_frame_left_ring"].ok
    assert rows["guarantee.inputs_differ_from_shifted_table"].value == 0
    assert rows["guarantee.state_differs_from_serial_replay"].value == 0
    assert rows["reference.frame_count_gap"].value == 0
    for name in ("reference.translation_gap", "reference.velocity_gap"):
        assert rows[name].ok and rows[name].limit > 0
    assert lobby.driver.scalars["checked_frame"] > 100


def test_spectator_is_the_serial_replay_at_its_own_frame(lobby):
    rows, d = lobby.rows, lobby.driver
    mine = [n for n in rows if ".spectator_" in n]
    if not d.spectator_apps:
        assert mine == []
        return
    assert rows["guarantee.spectator_state_differs_from_serial_replay"
                ].value == 0
    assert rows["reference.spectator_frame_count_gap"].value == 0
    for name in ("reference.spectator_translation_gap",
                 "reference.spectator_velocity_gap"):
        assert rows[name].ok and rows[name].limit > 0
    # Behind peer 0, and moving.
    frame = d.scalars["spectator_checked_frames"][0]
    assert 100 < frame <= d.a.frame
    lag = d.series["spectator_lag_frames"]
    assert len(lag) == d.scalars["ticks"] and min(lag) >= 0


def test_spectator_fanout_one_sample_an_advance_and_none_without(lobby):
    d = lobby.driver
    fanout = lobby.window.get("spectator_fanout_ms", [])
    if d.spectator_apps:
        assert len(fanout) == lobby.advances > 0
        # Inside session_advance: one of each an advance_frame().
        assert len(lobby.window["session_advance_ms"]) >= len(fanout)
    else:
        assert fanout == []
        assert "spectator_fanout_ms" not in d.program_metrics.series


def test_tick_stage_bytes_is_the_staged_arrays_nbytes(lobby):
    d = lobby.driver
    s = d.ctx.config["settings"]
    got = lobby.window["tick_stage_bytes"]
    assert len(got) == len(lobby.window["tick_io_buffers"]) > 0
    fused_calls = [b for b in got if b != ABSORB_BYTES]
    assert fused_calls == lobby.staged_window
    assert got.count(ABSORB_BYTES) == lobby.full_hits
    # branch_bits [B, F, P] of one byte is nearly all of it.
    tree = (int(s["speculation_branches"]) * int(s["speculation_frames"])
            * int(s["num_players"]))
    assert all(tree < b < tree + 1024 for b in fused_calls)


def test_rollback_depth_observed_once_a_rollback(lobby):
    depth = lobby.window.get("rollback_depth", [])
    assert len(depth) == lobby.rollbacks
    window = int(lobby.driver.ctx.config["settings"]["max_prediction"])
    assert all(1 <= n <= window + 1 for n in depth)
    assert float(np.median(depth)) >= 2     # a load and the new frame


def test_no_executable_is_built_after_warm_up(lobby):
    assert lobby.built == 0
    assert lobby.driver.scalars["ticks"] >= 100

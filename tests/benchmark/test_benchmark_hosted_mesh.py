"""What PR 39 adds to the benchmark as code: loop kind ``match_server_mesh``
(the shifted table its ``check()`` replays, the wiring of a lobby's far
ends, its refusal of a program that cannot run the cell) and the five
metric files.

The cells themselves (``hosted8.wan``, ``boids256.quarter``) are rehearsed
end to end, traced and under both controls by
``test_benchmark_rehearsal.py``, which takes its cases from
``BENCHMARK.json``; the program at the shape is held by
``tests/test_serve_hosted_mesh.py``.
"""

import contextlib
import importlib
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.drivers import match_server_mesh
from benchmark.drivers.common import Context
from benchmark.drivers.match_server_mesh import (
    Driver, _ResimulatingFarEnd, _ShiftedKeys,
)
from benchmark.drivers.p2p_mesh import shifted_table
from benchmark.inputs import HeldKeys
from benchmark.readers.common import Results

CELL = "hosted8.wan"
NEW_METRICS = {
    "poll_endpoints.serve": ["server256.wan", CELL],
    "branch_build_ms.serve": ["server256.wan", CELL],
    "spec_miss_share.serve": ["server256.wan", CELL],
    "spec_partial_hit_share.serve": ["server256.wan", CELL],
    "rollback_tick_share.serve": [CELL],
}


@pytest.fixture(scope="module")
def lobby():
    """The cell's driver at its toy size, set up (every session RUNNING)
    and not yet measured."""
    from bevy_ggrs_tpu import spec_runner

    _, _, config, traffic = run.load_cell(CELL, run.load_toy(CELL))
    title = importlib.import_module(f"benchmark.titles.{config['title']}")
    ctx = Context(
        config=config, traffic=traffic, seed=2**31 + 39, trace=False,
        control=None, title=title,
        annotate=lambda name: contextlib.nullcontext(),
        reference=importlib.import_module(
            f"benchmark.reference.{title.REFERENCE}"))
    d = Driver(ctx)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(spec_runner, "_blocking_ms", lambda call, reps=3: 0.0)
        d.setup()
    return d


def test_the_checks_table_is_the_generators_shifted_by_the_delay():
    _, _, _, traffic = run.load_cell(CELL)
    held = HeldKeys(7, 3, 8, traffic["inputs"])
    keys = _ShiftedKeys(held, 2)
    raw, got = held.table(40), keys.table(40)
    assert got.shape == raw.shape and got.shape[:2] == (3, 8)
    assert not got[..., :2].any()                  # frames 0 .. d-1 blank
    assert np.array_equal(got[..., 2:], raw[..., :-2])
    assert np.array_equal(got, shifted_table(raw, 2))
    # No delay: the table as it is (a hosted duel's check).
    assert np.array_equal(_ShiftedKeys(held, 0).table(40), raw)


def test_a_lobbys_far_ends_are_wired_as_a_full_mesh(lobby):
    d = lobby
    players = d.players
    assert (players, d.delay) == (8, 2)
    lobbies = len(d.live)
    assert len(d.hosts) == len(d.far_of) == lobbies
    assert len(d.far) == lobbies * (players - 1)
    assert d.scalars["remote_endpoints"] == lobbies * (players - 1)
    plans = set()
    for k, (host, ends) in enumerate(zip(d.hosts, d.far_of)):
        # The server's seat is player 0; it polls one endpoint a remote.
        assert host.local_player_handles() == [0]
        assert host.num_endpoints == players - 1
        assert host.max_prediction == 12
        assert [f.session.local_player_handles() for f in ends] == [
            [h] for h in range(1, players)]
        for h, far in enumerate(ends, start=1):
            assert far.session.num_endpoints == players - 1
            assert far.session.socket.addr == ("peer", k, h)
            assert far.feed(5, h) == d.held.bits(k, 5, h)
            plans.add(far.session.socket.plan)
            # Peer 1 of a sampled lobby resimulates; nobody else does.
            resimulates = h == 1 and k in d.sample
            assert isinstance(far, _ResimulatingFarEnd) == resimulates
            assert (far.runner is not None) == resimulates
    assert len(plans) == len(d.far)          # a burst plan a far end
    assert len(d.sample) == 2


def test_a_program_that_reads_a_branch_by_a_select_chain_is_refused(
        monkeypatch):
    from bevy_ggrs_tpu import state

    Driver._refuse_a_select_chain(1024)      # this program: runs
    monkeypatch.delattr(state, "SELECT_ROWS")
    Driver._refuse_a_select_chain(16)        # a toy size runs on any program
    with pytest.raises(RuntimeError, match="chain of 1024 selects"):
        Driver._refuse_a_select_chain(1024)
    assert match_server_mesh.Driver is Driver


def _spec(name):
    with open(os.path.join(run.HERE, "layer_metrics", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


def _read(name, results):
    spec = _spec(name)
    return importlib.import_module(
        f"benchmark.readers.{spec['kind']}").read(spec, results)


def test_the_new_metric_files_load_and_read():
    with open(os.path.join(run.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    results = Results(
        window_s=1.0, series={}, scalars={},
        counters={"rollbacks_total": 200, "spec_hits": 120,
                  "spec_partial_hits": 10, "spec_misses": 70,
                  "match_frames_advanced": 600},
        program_series={"serve_endpoints_polled": [112.0, 112.0, 105.0],
                        "serve_branch_build_ms": [2.0, 4.0, 3.0]})
    for name, cells in NEW_METRICS.items():
        assert entries[name]["workloads"] == cells
        assert entries[name]["moves"] == "match_frames_per_s"
    assert _read("poll_endpoints.serve", results) == 112.0
    assert _read("branch_build_ms.serve", results) == 3.0
    assert _read("spec_miss_share.serve", results) == 35.0
    assert _read("spec_partial_hit_share.serve", results) == 5.0
    assert _read("rollback_tick_share.serve", results) == pytest.approx(100 / 3)
    # A program without the series, a loop kind without the counter (the
    # parent; ``match_server_p2p``): nothing, and nothing raised.
    results.program_series.clear()
    results.counters.pop("match_frames_advanced")
    for name in ("poll_endpoints.serve", "branch_build_ms.serve",
                 "rollback_tick_share.serve"):
        assert _read(name, results) is None
    assert run.read_metrics(
        [{"name": "poll_endpoints.serve", "unit": "endpoints"}],
        "layer_metrics", results) == {}

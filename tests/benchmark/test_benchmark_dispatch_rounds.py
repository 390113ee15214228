"""``dispatch_rounds.serve`` (PR 34): the dispatch rounds a group tick of
the served path ran, from the program's series ``serve_rounds``."""

import os

import pytest

from benchmark import run
from benchmark.readers import metrics_series
from benchmark.readers.common import Results

ROOT = run.ROOT
NAME = "dispatch_rounds.serve"
SERVER_CELLS = ["server256.synctest", "server256.quarter", "server256.wan"]


def _spec():
    return run._load(
        os.path.join(ROOT, "benchmark", "layer_metrics", NAME + ".json"))


def _results(program_series):
    return Results(window_s=1.0, series={}, scalars={}, counters={},
                   program_series=program_series)


def test_the_metric_file_loads_and_reads_the_median_of_the_series():
    spec = _spec()
    assert spec["kind"] == "metrics_series" and spec["key"] == "serve_rounds"
    one = _results({"serve_rounds": [1, 1, 1, 2, 1]})
    assert metrics_series.read(spec, one) == 1
    two = _results({"serve_rounds": [2, 2, 2, 2]})
    assert metrics_series.read(spec, two) == 2


def test_a_program_without_the_series_leaves_the_metric_out():
    # The parent of PR 34 observes no ``serve_rounds``: the reader returns
    # nothing and does not raise, and run.py's line leaves the metric out.
    parent = _results({"serve_arg_assembly": [0.4, 0.5]})
    assert metrics_series.read(_spec(), parent) is None
    entry = {"name": NAME, "unit": "rounds"}
    assert run.read_metrics([entry], "layer_metrics", parent) == {}
    assert run.read_metrics([entry], "layer_metrics",
                            _results({"serve_rounds": [1, 1]})) == {
        NAME: {"value": 1, "unit": "rounds"}}


def test_the_manifest_lists_it_for_the_server_cells_alone():
    manifest = run._load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = manifest["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "rounds", "better": "lower",
                     "source": "program_counter", "layer": "executors",
                     "moves": "match_frames_per_s",
                     "workloads": SERVER_CELLS}
    for cell in [w["name"] for w in manifest["workloads"]]:
        names = {m["name"]
                 for m in run.metric_entries(manifest, "per_layer", cell)}
        assert (NAME in names) == (cell in SERVER_CELLS)


@pytest.mark.parametrize("cell", SERVER_CELLS)
def test_every_server_cell_reads_one_round_a_group_tick(cell):
    """The toy rehearsal, traced, on the CPU (a count, not a time): a
    SyncTest frame is one Load-delimited list since PR 34, as a hosted
    P2P frame always was."""
    lines = []
    rc, result = run.run_cell(cell, 2**31 + 34, 1.0, True,
                              require_tpu=False, overrides=run.load_toy(cell),
                              emit=lines.append)
    assert rc == 0 and result["correct"] is True
    assert result["metrics"][NAME] == {"value": 1, "unit": "rounds"}

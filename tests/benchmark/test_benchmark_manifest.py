"""BENCHMARK.json keeps to the contract's shape, and every name in it
resolves to a file of its own."""

import copy
import json
import os

import pytest

from benchmark import manifest as checker

ROOT = checker.ROOT


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def test_the_committed_manifest_is_valid():
    assert checker.validate(_manifest()) == []


# What PR 23 was accepted with. Later PRs append cells and configurations;
# these stay, in this order, with their configuration, mix and file.
ACCEPTED_CELLS = [
    ("server256.synctest", "box_game_server256", "synctest"),
    ("client.wan", "box_game_p2p_client", "wan"),
    ("client.lan", "box_game_p2p_client", "lan"),
    ("server256.quarter", "box_game_server256", "quarter"),
]
ACCEPTED_CONFIGS = {
    "box_game_p2p_client": "benchmark/configs/box_game_p2p_client.json",
    "box_game_server256": "benchmark/configs/box_game_server256.json",
}


def test_cells_and_configurations_of_the_issue():
    m = _manifest()
    assert m["command"] == ["python3", "benchmark/run.py"]
    first = m["workloads"][:len(ACCEPTED_CELLS)]
    assert [(w["name"], w["config"], w["traffic"])
            for w in first] == ACCEPTED_CELLS
    assert all(w["chips"] == 1 for w in first)
    files = {c["name"]: c["file"] for c in m["configs"]}
    for name, file in ACCEPTED_CONFIGS.items():
        assert files.get(name) == file
        assert os.path.isfile(os.path.join(ROOT, file))


def test_full_check_fits_the_budget_with_24_cells():
    rs = _manifest()["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def _break(fn):
    m = copy.deepcopy(_manifest())
    fn(m)
    return checker.validate(m)


@pytest.mark.parametrize("name,fn", [
    ("slash in a metric name",
     lambda m: m["end_to_end"][0].update(name="match_frames/s")),
    ("space in a unit",
     lambda m: m["end_to_end"][0].update(unit="frames per s")),
    ("extra key on a metric",
     lambda m: m["per_layer"][0].update(why="because")),
    ("moves an unknown metric",
     lambda m: m["per_layer"][0].update(moves="nothing")),
    ("a cell that does not report what the metric moves",
     lambda m: m["per_layer"][0].update(workloads=["client.wan"])),
    ("bound over a quarter",
     lambda m: m["end_to_end"][0].update(bound=0.5)),
    ("no setup_s",
     lambda m: m["end_to_end"].pop()),
    ("four chips",
     lambda m: [w.update(chips=4) for w in m["workloads"]]),
    ("traffic without a file",
     lambda m: m["workloads"][0].update(traffic="missing_mix")),
    ("unused configuration",
     lambda m: m["configs"].append(dict(m["configs"][0], name="spare",
                                        file="benchmark/peaks.json"))),
    ("run_seconds too long",
     lambda m: m.update(run_seconds=52)),
    ("a path that leaves the repo",
     lambda m: m.update(paths=["../elsewhere"])),
    ("per-layer metric without a data file",
     lambda m: m["per_layer"][0].update(name="no_such_metric")),
])
def test_broken_manifests_are_refused(name, fn):
    assert _break(fn), name


def test_every_configuration_states_guarantees_and_limits():
    for c in _manifest()["configs"]:
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["guarantees"] and cfg["precision"] == "float32"
        for spec in cfg["limits"].values():
            assert spec["limit"] > 0 and spec["readings"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "drivers", cfg["driver"] + ".py"))
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "reference", cfg["title"] + "_np.py"))


def test_peaks_are_keyed_by_device_kind_with_a_source():
    with open(os.path.join(ROOT, "benchmark", "peaks.json"),
              encoding="utf-8") as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in v5e["source"]

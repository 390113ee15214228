"""Each loop kind driven through run.py at a toy size on the CPU.

A rehearsal of control flow and of the ``correct`` decision, labelled
``platform: cpu`` by the result line itself: never a device number. The
look for a chip is skipped through ``run_cell(require_tpu=False)``, which no
command-line flag reaches.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run

ROOT = run.ROOT
TOY = {
    "p2p_pair": {
        "config": {"settings": {"speculation_branches": 8}},
        "traffic": {"warmup_ticks": 60, "trace_window_s": 0.5,
                    "traced_run_s": 1.0},
    },
    "match_server": {
        "config": {"settings": {"capacity": 8, "stagger_groups": 2}},
        "traffic": {"occupancy": {"admit": 8, "live": 8}, "sample_slots": 2,
                    "warmup_frames": 4, "trace_window_s": 0.4,
                    "traced_run_s": 1.0},
    },
}
QUARTER_TOY = {
    "config": {"settings": {"capacity": 8, "stagger_groups": 2}},
    "traffic": {"occupancy": {"admit": 8, "live": 4}, "sample_slots": 2,
                "warmup_frames": 4},
}


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)["workloads"]


def _toy(cell):
    _, _, config, traffic = run.load_cell(cell)
    if traffic["name"] == "quarter":
        return QUARTER_TOY
    return TOY[config["driver"]]


def _run(cell, seed=2**31 + 17, trace=False, control=None, seconds=1.0):
    lines = []
    rc, result = run.run_cell(cell, seed, seconds, trace, control=control,
                              require_tpu=False, overrides=_toy(cell),
                              emit=lines.append)
    assert rc == 0
    assert json.loads(lines[-1]) == json.loads(json.dumps(result))
    return result, [json.loads(x) for x in lines[:-1]]


@pytest.mark.parametrize("cell", [w["name"] for w in _cells()])
def test_cell_rehearsal_end_to_end(cell):
    result, info = _run(cell)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, info
    assert result["attempted"] > 0 and result["failed"] == 0
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    want = {m["name"] for m in manifest["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    compares = [i for i in info if i["info"] == "compare"]
    assert {"window.executables_built", "reference.translation_gap",
            "reference.velocity_gap"} <= {c["name"] for c in compares}
    assert all("limit" in c and "value" in c for c in compares)


@pytest.mark.parametrize("cell", ["server256.synctest", "client.wan"])
def test_cell_rehearsal_traced(cell):
    result, _ = _run(cell, trace=True)
    assert result["correct"] is True
    assert {"busy_s", "window_s"} <= set(result["device"])
    # The profiler is on for the first part of the traced run only.
    assert 0.3 < result["device"]["window_s"] < 0.9
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    allowed = {m["name"] for m in manifest["per_layer"]
               if cell in m.get("workloads", [cell])}
    host_side = {m["name"] for m in manifest["per_layer"]
                 if cell in m.get("workloads", [cell])
                 and m["source"] in ("host_clock", "program_span")}
    got = set(result["metrics"])
    assert got <= allowed          # nothing of another cell, no e2e metric
    # The host's timers need no device; a one-second toy window may hold no
    # rollback, and the readers of its recovery times then return nothing.
    assert {n for n in host_side if not n.startswith("recovery_ms")} <= got


@pytest.mark.parametrize("cell", ["server256.synctest", "client.wan"])
def test_lower_precision_control_is_not_correct(cell):
    result, info = _run(cell, control="bf16_state", seconds=2.0)
    failed = {i["name"] for i in info
              if i["info"] == "compare" and not i["ok"]}
    assert result["correct"] is False
    # Both sides round alike, so the bitwise guarantee holds: the plain
    # reference alone fails the run.
    assert failed and all(n.startswith("reference.") for n in failed)


@pytest.mark.parametrize("cell", ["server256.synctest", "client.wan"])
def test_broken_step_is_not_correct(cell):
    result, info = _run(cell, control="freeze_last_player")
    assert result["correct"] is False
    assert any(i["info"] == "compare" and not i["ok"]
               and i["name"] == "reference.translation_gap" for i in info)


def test_without_a_tpu_nothing_is_run(capsys):
    lines = []
    rc, result = run.run_cell("client.wan", 1, 1.0, False,
                              emit=lines.append)
    assert rc == 2 and result is None and not lines
    assert "needs 1 TPU" in capsys.readouterr().err


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in manifest["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, *manifest["command"][1:], "--workload",
         "client.wan", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout

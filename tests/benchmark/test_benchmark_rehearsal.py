"""Each loop kind driven through run.py at a toy size on the CPU.

A rehearsal of control flow and of the ``correct`` decision, labelled
``platform: cpu`` by the result line itself: never a device number. The
look for a chip is skipped through ``run_cell(require_tpu=False)``, which no
command-line flag reaches. The toy sizes are data: ``benchmark/toy/<cell>.json``
where a cell has its own, else ``benchmark/toy/<loop kind>.json``
(``run.load_toy``).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run

ROOT = run.ROOT


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)["workloads"]


def _first_cell_of_every_loop_kind():
    """A new loop kind is rehearsed traced, under the lower-precision
    control and with a broken step from the moment its first cell lands."""
    first = {}
    for w in _cells():
        first.setdefault(run.load_cell(w["name"])[2]["driver"], w["name"])
    return list(first.values())


def _run(cell, seed=2**31 + 17, trace=False, control=None, seconds=1.0):
    lines = []
    rc, result = run.run_cell(cell, seed, seconds, trace, control=control,
                              require_tpu=False, overrides=run.load_toy(cell),
                              emit=lines.append)
    assert rc == 0
    assert json.loads(lines[-1]) == json.loads(json.dumps(result))
    return result, [json.loads(x) for x in lines[:-1]]


@pytest.mark.parametrize("cell", [w["name"] for w in _cells()])
def test_cell_rehearsal_end_to_end(cell, capfd):
    result, info = _run(cell)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
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
    # The same numbers in the result's line, as its last key, and as the
    # last lines of standard error.
    assert result["compared"] == {c["name"]: {"value": c["value"],
                                              "limit": c["limit"]}
                                  for c in compares}
    err = capfd.readouterr().err.strip().splitlines()[-len(compares):]
    assert [line.split()[:2] for line in err] == [
        ["compared", c["name"]] for c in compares]
    assert all(line.endswith(" ok") for line in err)


@pytest.mark.parametrize("cell", _first_cell_of_every_loop_kind())
def test_cell_rehearsal_traced(cell):
    result, _ = _run(cell, trace=True)
    assert result["correct"] is True
    assert {"busy_s", "window_s"} <= set(result["device"])
    # The profiler is on for the first part of the traced run only.
    assert 0.3 < result["device"]["window_s"] < 0.9
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(result)[-1] == "compared"
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


@pytest.mark.parametrize("cell", _first_cell_of_every_loop_kind())
def test_lower_precision_control_is_not_correct(cell):
    result, info = _run(cell, control="bf16_state", seconds=2.0)
    failed = {i["name"] for i in info
              if i["info"] == "compare" and not i["ok"]}
    assert result["correct"] is False
    # Both sides round alike, so the bitwise guarantee holds: the plain
    # reference alone fails the run.
    assert failed and all(n.startswith("reference.") for n in failed)


@pytest.mark.parametrize("cell", _first_cell_of_every_loop_kind())
def test_broken_step_is_not_correct(cell):
    result, info = _run(cell, control="freeze_last_player")
    assert result["correct"] is False
    assert any(i["info"] == "compare" and not i["ok"]
               and i["name"] == "reference.translation_gap" for i in info)


def test_a_cell_without_a_toy_size_names_the_files_to_add(monkeypatch):
    monkeypatch.setattr(run, "toy_files", lambda cell, kind: [
        f"benchmark/toy/absent.{cell}.json", f"benchmark/toy/absent.{kind}.json"])
    with pytest.raises(FileNotFoundError) as err:
        run.load_toy("client.lan")
    assert "benchmark/toy/absent.client.lan.json" in str(err.value)
    assert "benchmark/toy/absent.p2p_pair.json" in str(err.value)


def test_a_cells_own_toy_size_comes_before_its_loop_kinds():
    kind = run.load_toy("server256.synctest")
    own = run.load_toy("server256.quarter")
    assert kind["traffic"]["occupancy"] == {"admit": 8, "live": 8}
    assert own["traffic"]["occupancy"] == {"admit": 8, "live": 4}
    assert set(kind) == set(own) == {"config", "traffic"}


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

"""A later PR adds a deployment as NEW files and ``BENCHMARK.json`` entries
and edits nothing that is here, the tests included.

The proof is the experiment itself, kept: in a temporary copy of the tree a
configuration, a loop kind (a module that re-exports an existing ``Driver``),
a traffic mix, a toy size, a cell and the cell's name in the metrics'
``workloads`` lists are added, and nothing else is touched. The manifest is
then valid, and the tests of the copy that read the manifest (the manifest's
own, the reference's control, the rehearsals) pass with the new entries
among their cases: the new cell runs to ``correct: true``, and as the first
cell of a new loop kind it is also rehearsed traced, under the
lower-precision control and with a broken step.
"""

import filecmp
import json
import os
import re
import shutil
import subprocess
import sys

from benchmark import manifest as checker

ROOT = checker.ROOT
CONFIG, KIND, MIX, CELL = ("files_only_server", "files_only_loop",
                           "files_only_half", "files_only.half")
TWIN_OF = "server256.quarter"      # the accepted cell the new one is made from


def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


def _copy_of_the_tree(tmp):
    """What a checkout holds of the benchmark, its tests and the program
    (the program is linked, not copied: nothing of it is edited)."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    for name in ("BENCHMARK.json", "pyproject.toml"):
        shutil.copy(os.path.join(ROOT, name), tmp)
    for p in manifest["paths"]:
        shutil.copytree(os.path.join(ROOT, p), os.path.join(tmp, p),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "tests", "conftest.py"),
                os.path.join(tmp, "tests"))
    os.symlink(os.path.join(ROOT, "bevy_ggrs_tpu"),
               os.path.join(tmp, "bevy_ggrs_tpu"))
    return manifest


def _add_a_deployment(tmp, manifest):
    """New files and new entries only. Returns the files added."""
    bench = os.path.join(tmp, "benchmark")
    twin = next(w for w in manifest["workloads"] if w["name"] == TWIN_OF)
    entry = next(c for c in manifest["configs"] if c["name"] == twin["config"])
    config = _load(os.path.join(ROOT, entry["file"]))
    toy = _load(os.path.join(bench, "toy", config["driver"] + ".json"))
    added = {
        f"benchmark/configs/{CONFIG}.json": dict(
            config, name=CONFIG, driver=KIND,
            source="a test's stand-in for the next deployment's source"),
        f"benchmark/traffic/{MIX}.json": dict(
            _load(os.path.join(bench, "traffic", twin["traffic"] + ".json")),
            name=MIX, occupancy={"admit": 256, "live": 128}),
        f"benchmark/toy/{KIND}.json": dict(
            toy, what="toy size of the added loop kind: 8 admitted, 4 stay",
            traffic=dict(toy["traffic"], occupancy={"admit": 8, "live": 4})),
    }
    for rel, content in added.items():
        _dump(content, os.path.join(tmp, rel))
    driver = f"benchmark/drivers/{KIND}.py"
    with open(os.path.join(tmp, driver), "w", encoding="utf-8") as f:
        f.write('"""A loop kind added as a file: the operator\'s loop."""\n'
                f"from benchmark.drivers.{config['driver']} import Driver"
                "  # noqa: F401\n")
    manifest["configs"].append(dict(
        entry, name=CONFIG, file=f"benchmark/configs/{CONFIG}.json",
        source=added[f"benchmark/configs/{CONFIG}.json"]["source"]))
    manifest["workloads"].append(dict(
        twin, name=CELL, config=CONFIG, traffic=MIX,
        why="half of 256 matches live, closed loop: added by files alone"))
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if TWIN_OF in m.get("workloads", []):
                m["workloads"].append(CELL)
    return sorted([*added, driver])


def _files(top):
    out = set()
    for folder, _, names in os.walk(top):
        if "__pycache__" not in folder:
            out.update(os.path.relpath(os.path.join(folder, n), top)
                       for n in names)
    return out


def test_a_deployment_is_added_by_new_files_and_entries_alone(tmp_path):
    tmp = str(tmp_path)
    manifest = _copy_of_the_tree(tmp)
    accepted = json.loads(json.dumps(manifest))
    added = _add_a_deployment(tmp, manifest)

    # Without its toy size the cell is refused, by the name of the file.
    toy = f"benchmark/toy/{KIND}.json"
    os.rename(os.path.join(tmp, toy), os.path.join(tmp, "toy.aside"))
    problems = checker.validate(manifest, root=tmp)
    assert len(problems) == 1 and toy in problems[0], problems
    assert f"benchmark/toy/{CELL}.json" in problems[0]
    os.rename(os.path.join(tmp, "toy.aside"), os.path.join(tmp, toy))
    assert checker.validate(manifest, root=tmp) == []
    _dump(manifest, os.path.join(tmp, "BENCHMARK.json"))

    # Nothing that was there is edited: every accepted entry of the manifest
    # and every file of the benchmark and its tests is as it was.
    for group in ("configs", "workloads"):
        assert manifest[group][:len(accepted[group])] == accepted[group]
    for key in ("command", "paths", "run_seconds"):
        assert manifest[key] == accepted[key]
    for p in accepted["paths"]:
        was = _files(os.path.join(ROOT, p))
        now = _files(os.path.join(tmp, p))
        assert was <= now
        assert sorted(os.path.join(p, f) for f in now - was) == [
            f for f in added if f.startswith(p + "/")]
        _, differ, errors = filecmp.cmpfiles(
            os.path.join(ROOT, p), os.path.join(tmp, p), sorted(was),
            shallow=False)
        assert not differ and not errors

    # The copy's own tests, as they stand, with the new entries among their
    # cases. The compile cache is this process's, so nothing compiles twice.
    from bevy_ggrs_tpu.utils import xla_cache

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=(
                   xla_cache.ensure_persistent_compilation_cache()))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "-rA",
         "tests/benchmark/test_benchmark_manifest.py",
         "tests/benchmark/test_benchmark_reference.py",
         "tests/benchmark/test_benchmark_rehearsal.py",
         "-k", f"manifest or {CONFIG} or {CELL}"],
        cwd=tmp, env=env, capture_output=True, text=True, timeout=600)
    tail = proc.stdout[-6000:] + proc.stderr[-2000:]
    assert proc.returncode == 0, tail
    passed = set(re.findall(r"^PASSED (\S+)", proc.stdout, flags=re.M))
    rehearsal = "tests/benchmark/test_benchmark_rehearsal.py::"
    for test in ("test_cell_rehearsal_end_to_end", "test_cell_rehearsal_traced",
                 "test_lower_precision_control_is_not_correct",
                 "test_broken_step_is_not_correct"):
        assert f"{rehearsal}{test}[{CELL}]" in passed, tail
    for seed in (0, 1, 2):
        assert ("tests/benchmark/test_benchmark_reference.py::"
                "test_bfloat16_control_fails_the_limits_and_float32_passes"
                f"[{seed}-{CONFIG}]") in passed, tail
    assert ("tests/benchmark/test_benchmark_manifest.py::"
            "test_cells_and_configurations_of_the_issue") in passed, tail
    assert ("tests/benchmark/test_benchmark_manifest.py::"
            "test_the_committed_manifest_is_valid") in passed, tail

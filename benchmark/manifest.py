"""Checks ``BENCHMARK.json`` against the contract's shape and this
harness's own rule that every name resolves to a file. Returns a list of
problems (empty: fine). Used by tests/benchmark; run by hand as

    python3 benchmark/manifest.py
"""

from __future__ import annotations

import importlib
import json
import os
import re
import sys
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def toy_files(workload: str, loop_kind: str, top: str = "benchmark") -> list:
    """Where a cell's toy size (what the CPU rehearsal shrinks it to) may
    stand, relative to the checkout: the cell's own file first, its loop
    kind's second."""
    return [f"{top}/toy/{name}.json" for name in (workload, loop_kind)]


def _line(text, limit=200) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def validate(manifest: dict, root: str = ROOT) -> List[str]:
    bad: List[str] = []
    say = bad.append
    if set(manifest) != KEYS["top"]:
        say(f"top-level keys are {sorted(manifest)}")
        return bad
    if len(json.dumps(manifest)) > 64 * 1024:
        say("file over 64 KiB")
    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16 or not all(
            PATH.match(p) and not p.startswith("/") and ".." not in p
            for p in paths):
        say(f"paths {paths}")
    under = lambda f: any(  # noqa: E731
        f == p or f.startswith(p.rstrip("/") + "/") for p in paths)
    cmd = manifest["command"]
    if not 1 <= len(cmd) <= 32 or not all(_line(w) for w in cmd):
        say("command")
    for w in cmd:
        if w.startswith("/") or ".." in w.split("/"):
            say(f"command word {w!r} leaves the repo")
        if os.path.exists(os.path.join(root, w)) and not under(w):
            say(f"command word {w!r} is a file outside paths")
    if not (isinstance(manifest["run_seconds"], int)
            and 1 <= manifest["run_seconds"] <= 51):
        say("run_seconds")

    def entries(group, lo, hi, optional=()):
        items = manifest[group]
        if not lo <= len(items) <= hi:
            say(f"{group}: {len(items)} entries")
        names = [e.get("name") for e in items]
        if len(set(names)) != len(names):
            say(f"{group}: duplicate names")
        for e in items:
            extra = set(e) - KEYS[group] - set(optional)
            missing = KEYS[group] - set(e)
            if extra or missing:
                say(f"{group} {e.get('name')}: extra {sorted(extra)} "
                    f"missing {sorted(missing)}")
            if not NAME.match(str(e.get("name", ""))):
                say(f"{group}: bad name {e.get('name')!r}")
        return items

    configs = entries("configs", 1, 24)
    cells = entries("workloads", 1, 24)
    e2e = entries("end_to_end", 1, 16, optional=("workloads",))
    layer = entries("per_layer", 1, 128, optional=("workloads",))
    if bad:
        return bad

    files = [c["file"] for c in configs]
    if len(set(files)) != len(files):
        say("two configurations share a file")
    loop_kinds = {}
    for c in configs:
        if not (_line(c["source"]) and _line(c["why"])):
            say(f"config {c['name']}: source or why")
        if len(c["reduced"]) > 16 or not all(NAME.match(k)
                                             for k in c["reduced"]):
            say(f"config {c['name']}: reduced")
        if not (under(c["file"]) and PATH.match(c["file"])
                and os.path.isfile(os.path.join(root, c["file"]))):
            say(f"config {c['name']}: file {c['file']}")
            continue
        try:
            with open(os.path.join(root, c["file"]), encoding="utf-8") as f:
                loop_kinds[c["name"]] = json.load(f)["driver"]
        except (ValueError, KeyError, TypeError):
            pass    # not a configuration's file: the tests of its content say so
    config_names = {c["name"] for c in configs}
    used = set()
    pairs = set()
    for w in cells:
        if w["config"] not in config_names:
            say(f"cell {w['name']}: unknown config")
        used.add(w["config"])
        if not NAME.match(w["traffic"]) or not _line(w["why"]):
            say(f"cell {w['name']}: traffic or why")
        if w["chips"] not in (1, 4):
            say(f"cell {w['name']}: chips")
        if (w["config"], w["traffic"]) in pairs:
            say(f"cell {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if not any(os.path.isfile(os.path.join(
                root, paths[0], "traffic", w["traffic"] + s))
                for s in DATA_SUFFIXES):
            say(f"cell {w['name']}: no traffic file")
        kind = loop_kinds.get(w["config"])
        toys = toy_files(w["name"], kind, paths[0])
        if kind and not any(os.path.isfile(os.path.join(root, t))
                            for t in toys):
            say(f"cell {w['name']}: no toy size for the rehearsal: add "
                f"{toys[1]} or {toys[0]}")
    if used != config_names:
        say(f"configs no cell uses: {sorted(config_names - used)}")
    four = sum(1 for w in cells if w["chips"] == 4)
    if four > max(1, len(cells) // 2):
        say("too many four-chip cells")

    cell_names = {w["name"] for w in cells}
    metric_names = [m["name"] for m in e2e + layer]
    if len(set(metric_names)) != len(metric_names):
        say("two metrics share a name")

    def cells_of(m):
        return set(m.get("workloads", cell_names))

    for m in e2e + layer:
        if not UNIT.match(m["unit"]):
            say(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            say(f"metric {m['name']}: better")
        if m["source"] not in SOURCES:
            say(f"metric {m['name']}: source")
        if not cells_of(m) <= cell_names or not cells_of(m):
            say(f"metric {m['name']}: workloads")
    if "setup_s" not in {m["name"] for m in e2e}:
        say("no setup_s")
    for m in e2e:
        if m["source"] not in ("host_clock", "device_trace"):
            say(f"end-to-end {m['name']}: source")
        if not (isinstance(m["bound"], (int, float))
                and 0.01 <= m["bound"] <= 0.25):
            say(f"end-to-end {m['name']}: bound {m['bound']}")
        if not os.path.isfile(os.path.join(root, paths[0], "end_to_end",
                                           m["name"] + ".json")):
            say(f"end-to-end {m['name']}: no data file")
    e2e_cells = {m["name"]: cells_of(m) for m in e2e}
    for m in layer:
        if not _line(m["layer"]):
            say(f"per-layer {m['name']}: layer")
        if m["moves"] not in e2e_cells:
            say(f"per-layer {m['name']}: moves {m['moves']!r}")
        elif not cells_of(m) <= e2e_cells[m["moves"]]:
            say(f"per-layer {m['name']}: a cell of it does not report "
                f"{m['moves']}")
        if "roofline" in m["name"] and m["unit"] != "%":
            say(f"per-layer {m['name']}: a roofline share is in %")
        spec_path = os.path.join(root, paths[0], "layer_metrics",
                                 m["name"] + ".json")
        if not os.path.isfile(spec_path):
            say(f"per-layer {m['name']}: no data file")
            continue
        with open(spec_path, encoding="utf-8") as f:
            kind = json.load(f).get("kind", "")
        try:
            importlib.import_module(f"benchmark.readers.{kind}")
        except ImportError:
            say(f"per-layer {m['name']}: no reader for kind {kind!r}")
    for w in cells:
        mine = [m for m in e2e if w["name"] in cells_of(m)]
        if not any(m["name"] == "setup_s" for m in mine) or len(mine) < 2:
            say(f"cell {w['name']}: needs setup_s and one more end-to-end")
        if not any(w["name"] in cells_of(m) for m in layer):
            say(f"cell {w['name']}: no per-layer metric")
    return bad


def main() -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        problems = validate(json.load(f))
    for p in problems:
        print("BENCHMARK.json:", p)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The dense flocking-force paths side by side, through the normal path
(ROADMAP S5 / D2; PERF.md section 6, PR 31's step 0).

    python3 tools/force_paths.py [--kernels xla,pallas,mxu] [--branches 128]
                                 [--seconds 10] [--seed 7]

Runs the benchmark's cell ``boids1k.wan`` (``GGRSPlugin.with_speculation`` +
``SessionBuilder.start_p2p_session``, two peers on loopback, 1,024 boids,
window 8) once traced and once untraced for each value of the public
``boids.make_schedule(kernel=...)``, the configuration's
``settings.force_kernel`` and ``speculation_branches`` overridden, one child
process a run (a chip belongs to one process; this parent stays off jax).
Per path it prints: did warm-up attest and did every ballot agree (the
cell's ``guarantee.*`` rows), the device time of one fused tick
(``tick_program_ms.client``) and of the force kernel in it
(``pairwise_kernel_ms.client``; nothing for ``xla``, whose fusions the
compiler names), ``frame_ms.p50``, and one step's gap to the NumPy reference
(``reference.*``). Every line a run printed goes to
``chiprun_out/force_paths/<kernel>_b<branches>.trace<0|1>.jsonl``. Needs the
TPU (``chiprun -- python3 tools/force_paths.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "boids1k.wan"
CHILD = """
import json, sys
sys.path.insert(0, {root!r})
from benchmark import run
rc, _ = run.run_cell({cell!r}, {seed}, {seconds}, {trace}, overrides={{
    "config": {{"settings": {{"force_kernel": {kernel!r},
                             "speculation_branches": {branches}}}}}}})
sys.exit(rc)
"""


def one_run(kernel: str, branches: int, seconds: float, seed: int,
            trace: bool, out_dir: str) -> dict:
    code = CHILD.format(root=ROOT, cell=CELL, seed=seed, seconds=seconds,
                        trace=trace, kernel=kernel, branches=branches)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True)
    path = os.path.join(out_dir, f"{kernel}_b{branches}.trace{int(trace)}")
    with open(path + ".jsonl", "w", encoding="utf-8") as f:
        f.write(proc.stdout)
    with open(path + ".err", "w", encoding="utf-8") as f:
        f.write(proc.stderr[-20000:])
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    if proc.returncode != 0 or not lines or "correct" not in lines[-1]:
        return {"rc": proc.returncode, "error": proc.stderr[-1500:]}
    result = lines[-1]
    info = next(x for x in lines if x.get("info") == "run")
    return {
        "rc": 0, "correct": result["correct"], "failed": result["failed"],
        "setup_s": info["setup_s"],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "compared": {k: v["value"] for k, v in result["compared"].items()},
        "device": result["device"],
        "device_ops": result.get("breakdown", {}).get("device_ops"),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--kernels", default="xla,pallas,mxu")
    parser.add_argument("--branches", default="128",
                        help="comma-separated speculation widths")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--seed", type=int, default=7)
    args = parser.parse_args(argv)
    out_dir = os.path.join(ROOT, "chiprun_out", "force_paths")
    os.makedirs(out_dir, exist_ok=True)
    table = {}
    for branches in (int(b) for b in args.branches.split(",")):
        for kernel in args.kernels.split(","):
            for trace in (True, False):
                row = one_run(kernel, branches, args.seconds, args.seed,
                              trace, out_dir)
                table[f"{kernel}_b{branches}.trace{int(trace)}"] = row
                print(json.dumps({"path": kernel, "branches": branches,
                                  "trace": int(trace), **row}), flush=True)
    with open(os.path.join(out_dir, "table.json"), "w",
              encoding="utf-8") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

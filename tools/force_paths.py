#!/usr/bin/env python3
"""The dense flocking-force paths side by side, through the normal path
(ROADMAP S5 / D2; PERF.md section 6, PR 31's and PR 49's step 0).

    python3 tools/force_paths.py [--cell boids1k.wan|boids256.synctest|...]
                                 [--kernels xla,mxu] [--branches 128]
                                 [--seconds 10] [--seed 7]
                                 [--close-frames N] [--count-only]

Runs a boids cell of the benchmark (``boids1k.wan``: ``GGRSPlugin`` + a P2P
session, two peers on loopback, 1,024 boids, window 8; ``boids256.synctest``:
one ``MatchServer`` hosting 256 matches of the same title) once traced and
once untraced for each value of the public ``boids.make_schedule(kernel=...)``,
the configuration's ``settings.force_kernel`` overridden (and
``speculation_branches`` where ``--branches`` is given), one child process a
run (a chip belongs to one process; this parent stays off jax). Per path it
prints: did warm-up attest and did every ballot agree (the cell's
``guarantee.*`` rows), the device time of one tick program
(``tick_program_ms.*``) and of the force kernel in it
(``pairwise_kernel_ms.*``; nothing for ``xla``, whose fusions the compiler
names), the cell's end-to-end metric, and one step's gap to the NumPy
reference (``reference.*``). Every line a run printed goes to
``chiprun_out/force_paths/<cell>.<kernel>_b<branches>.trace<0|1>.jsonl``.
Needs the TPU (``chiprun -- python3 tools/force_paths.py``).

First, on the host and outside any run (NumPy, the benchmark's plain
reference replaying the cell's own input mix from the common spawn): how
often the MXU kernel's close pairs' path engages. A pair closer than
``1 / CLOSE_W`` = 5e-3 leaves the matmul form (``ops/pairwise.py``
``_close_pair_sums``); the line ``close_pairs`` gives, over ``--close-frames``
frames of a few matches, the share of force evaluations, of 512- and
1,024-row block steps and of 128- and 64-row strips that hold such a pair.
``--count-only`` prints that line and stops (no chip needed).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CLOSE_DISTANCE = 1.0 / 200.0  # 1 / ops.pairwise.CLOSE_W
# Frames from the common spawn a run of the cell reaches: a served window's
# matches are 4-136 frames old (PERF.md section 7, "Left by PR 37" (e)), a
# client runs 1,680 (3 s of warm-up, 25 s of window), of which the count
# takes the first 600: the flock has spread by frame 200.
CLOSE_FRAMES = {"boids1k.wan": 600}
CLOSE_FRAMES_SERVED = 140
CHILD = """
import json, sys
sys.path.insert(0, {root!r})
from benchmark import run
rc, _ = run.run_cell({cell!r}, {seed}, {seconds}, {trace}, overrides={{
    "config": {{"settings": {settings!r}}}}})
sys.exit(rc)
"""


def close_pair_shares(cell: str, frames: int, seed: int,
                      matches: int = 4) -> dict:
    """Replay ``matches`` matches of the cell in the plain reference and
    count where a pair closer than ``CLOSE_DISTANCE`` sits."""
    import numpy as np

    from benchmark import run
    from benchmark.inputs import HeldKeys
    from benchmark.reference import boids_np as ref

    _, _, config, traffic = run.load_cell(cell)
    players = config["settings"]["num_players"]
    n = config["settings"]["num_entities"]
    table = HeldKeys(seed, matches, players, traffic["inputs"]).table(frames)
    pos, vel = ref.spawn(matches, players, n)
    heights = [h for h in (1024, 512, 128, 64) if n % h == 0]
    held = {h: 0 for h in heights}
    evaluations = with_pair = pairs = 0
    for f in range(frames):
        _, d2 = ref._pair_d2(pos, np.float32)
        close = (d2 < np.float32(CLOSE_DISTANCE) ** 2) & (d2 >= ref.SELF_D2)
        rows = close.any(-1)                               # [matches, n]
        evaluations += matches
        with_pair += int(rows.any(-1).sum())
        pairs += int(close.sum()) // 2
        for h in heights:
            held[h] += int(rows.reshape(matches, n // h, h).any(-1).sum())
        pos, vel = ref.step(pos, vel, table[:, :, f])
    return {
        "cell": cell, "frames": frames, "matches": matches,
        "closer_than": CLOSE_DISTANCE, "close_pairs_total": pairs,
        "evaluations_with_a_pair_share": with_pair / evaluations,
        **{f"rows{h}_share": held[h] / (evaluations * (n // h))
           for h in heights},
    }


def one_run(cell: str, kernel: str, branches, seconds: float, seed: int,
            trace: bool, out_dir: str) -> dict:
    settings = {"force_kernel": kernel}
    if branches is not None:
        settings["speculation_branches"] = branches
    code = CHILD.format(root=ROOT, cell=cell, seed=seed, seconds=seconds,
                        trace=trace, settings=settings)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True)
    path = os.path.join(
        out_dir, f"{cell}.{kernel}_b{branches or 'cfg'}.trace{int(trace)}")
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
    parser.add_argument("--cell", default="boids1k.wan",
                        help="a boids cell of BENCHMARK.json")
    parser.add_argument("--kernels", default="xla,mxu")
    parser.add_argument("--branches", default="",
                        help="comma-separated speculation widths "
                             "(default: the configuration's own)")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--close-frames", type=int, default=None,
                        help="frames of the host's close-pair count "
                             "(0: skip it)")
    parser.add_argument("--count-only", action="store_true")
    args = parser.parse_args(argv)
    out_dir = os.path.join(ROOT, "chiprun_out", "force_paths")
    os.makedirs(out_dir, exist_ok=True)
    frames = args.close_frames
    if frames is None:
        frames = CLOSE_FRAMES.get(args.cell, CLOSE_FRAMES_SERVED)
    table = {}
    if frames:
        table["close_pairs"] = close_pair_shares(args.cell, frames, args.seed)
        print(json.dumps({"close_pairs": table["close_pairs"]}), flush=True)
    if args.count_only:
        return 0
    widths = [int(b) for b in args.branches.split(",") if b] or [None]
    for branches in widths:
        for kernel in args.kernels.split(","):
            for trace in (True, False):
                row = one_run(args.cell, kernel, branches, args.seconds,
                              args.seed, trace, out_dir)
                key = f"{kernel}_b{branches or 'cfg'}.trace{int(trace)}"
                table[key] = row
                print(json.dumps({"cell": args.cell, "path": kernel,
                                  "branches": branches, "trace": int(trace),
                                  **row}), flush=True)
    with open(os.path.join(out_dir, f"{args.cell}.table.json"), "w",
              encoding="utf-8") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell once with a callback on the interpreter's garbage collector.

    python3 benchmark/tools/gc_probe.py <workload> <seed> <seconds>

Prints every full (generation 2) collection that happened from the start
of the process with its duration, and the run's usual lines. A player's
hitch of 60-130 ms (``late_tick_share``, the slowest tick of the run's
``scalars``) is one such collection (PERF.md section 5, PR 23).
"""

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from benchmark import run

    collections, started = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            started[0] = time.perf_counter()
        elif info["generation"] == 2:
            collections.append({
                "at_s": started[0] - run.T_PROCESS,
                "ms": (time.perf_counter() - started[0]) * 1e3,
                "collected": info["collected"]})

    gc.callbacks.append(on_gc)
    rc, _ = run.run_cell(argv[1], int(argv[2]), float(argv[3]), False)
    print(json.dumps({"info": "gc", "tracked_objects": len(gc.get_objects()),
                      "threshold": gc.get_threshold(),
                      "full_collections": collections}))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv))

#!/usr/bin/env python3
"""The spread of sets of runs, as the contract's bounds are set from it.

    python3 benchmark/tools/spread.py <set1.out> [<set2.out> ...]

Each file holds the standard output of several runs of one cell (one set);
every line that is a result object (has ``correct``) is one run. Per
metric and set: the values' median and their spread — the distance between
the first and third quartile (``statistics.quantiles(values, n=4)``) as a
share of the median. The bound is about five times the widest spread; the
driver's check for a bound that is too tight leaves out each set's run
farthest from the median and takes the mean of the sets' spreads, which is
printed beside it (a shared host stalls a run now and then).
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def results_of(path):
    out = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line.startswith("{") and '"correct"' in line:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
    return out


def main(argv) -> int:
    from benchmark.reduce import stats

    sets = [(p, results_of(p)) for p in argv[1:]]
    names = sorted({n for _, rs in sets for r in rs for n in r["metrics"]})
    for path, rs in sets:
        wrong = sum(1 for r in rs if not r["correct"])
        print(f"{path}: {len(rs)} runs, {wrong} not correct, failed "
              f"{[r['failed'] for r in rs]}")
    for name in names:
        widest, trimmed = 0.0, []
        for path, rs in sets:
            values = [r["metrics"][name]["value"] for r in rs
                      if name in r["metrics"]]
            if len(values) < 3:
                continue
            sp = stats.spread(values)
            widest = max(widest, sp)
            med = statistics.median(values)
            kept = sorted(values, key=lambda v: abs(v - med))[:-1]
            trimmed.append(stats.spread(kept))
            print(f"  {name:24s} {os.path.basename(path):24s} n={len(values)}"
                  f" median={med:.6g} spread={sp:.4%}"
                  f" without_farthest={trimmed[-1]:.4%}"
                  f" min={min(values):.6g} max={max(values):.6g}")
        if trimmed:
            mean_trimmed = sum(trimmed) / len(trimmed)
            print(f"  {name:24s} widest spread {widest:.4%}; mean of the sets'"
                  f" spreads without each set's farthest run "
                  f"{mean_trimmed:.4%} -> bound ~ "
                  f"{max(0.01, 5 * mean_trimmed):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

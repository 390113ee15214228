#!/usr/bin/env python3
"""Print what a ``jax.profiler`` trace holds: planes, lines, event counts and
the most frequent names. For looking at one trace by hand before writing a
pattern into a per-layer metric's data file.

    python3 benchmark/tools/trace_dump.py <xplane.pb> [names-per-line]
"""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from jax.profiler import ProfileData

    path = argv[1]
    top = int(argv[2]) if len(argv) > 2 else 8
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            names = collections.Counter()
            total = collections.Counter()
            first = last = None
            n = 0
            for ev in line.events:
                names[ev.name] += 1
                total[ev.name] += ev.duration_ns
                first = ev.start_ns if first is None else min(first, ev.start_ns)
                end = ev.start_ns + ev.duration_ns
                last = end if last is None else max(last, end)
                n += 1
            print(f"  LINE {line.name!r}: {n} events, {first} .. {last} ns")
            for name, _ in total.most_common(top):
                print(f"      {names[name]:7d} x {total[name] / 1e6:10.3f} ms  "
                      f"{name[:110]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

#!/usr/bin/env python
"""Look at one profiler trace by hand: its planes, their lines, and the
most frequent event names of each line.

    python benchmark/describe_trace.py <trace.xplane.pb>
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark.harness import xplane

    path = (argv or sys.argv[1:])[0]
    for dev in xplane.load(path, plane_prefix=""):
        print(f"PLANE {dev.plane}")
        for line, events in dev.lines.items():
            tot = xplane.totals(events)
            print(f"  LINE {line!r}: {len(events)} events, busy "
                  f"{xplane.busy_ns(events) / 1e9:.6f} s")
            for name, (n, ns) in sorted(tot.items(),
                                        key=lambda kv: -kv[1][1])[:12]:
                print(f"    {n:6d} x {ns / 1e9:10.6f} s  {name[:150]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Print what a profiler trace holds, to read it by hand.

    python bench/trace_dump.py path/to/x.xplane.pb

For each plane and line: the number of events and the ten names that take
most time, with their summed durations.
"""
from __future__ import annotations

import sys
from collections import defaultdict


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    path = (argv or sys.argv[1:])[0]
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            tot, n = defaultdict(float), 0
            for e in line.events:
                tot[e.name] += e.duration_ns * 1e-9
                n += 1
            top = sorted(tot.items(), key=lambda x: -x[1])[:10]
            print(f"  line {line.name!r}: {n} events")
            for name, t in top:
                print(f"    {t:10.6f} s  {name[:100]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

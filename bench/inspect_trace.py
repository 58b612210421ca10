#!/usr/bin/env python3
"""Print the layout of a JAX profiler trace: planes, lines, event names.

    python3 bench/inspect_trace.py <trace dir or .xplane.pb>

For reading a new trace by hand before changing ``bench/trace.py``.
"""

from __future__ import annotations

import sys
from collections import Counter
from pathlib import Path


def main(argv) -> None:
    from jax.profiler import ProfileData

    path = Path(argv[0])
    if path.is_dir():
        path = sorted(path.rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names = Counter(e.name for e in evs)
            tot = Counter()
            for e in evs:
                tot[e.name] += e.duration_ns
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{len(names)} names")
            for name, ns in tot.most_common(12):
                print(f"    {ns / 1e6:12.3f} ms  x{names[name]:<6d} {name}")
            for e in evs[:2]:
                print(f"    first: {e.name} @{e.start_ns} +{e.duration_ns} "
                      f"stats={[(k, v) for k, v in e.stats][:8]}")


if __name__ == "__main__":
    main(sys.argv[1:])

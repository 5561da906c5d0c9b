#!/usr/bin/env python3
"""Print the structure of a profiler trace: planes, lines, the most
frequent event names of each line with their stats.  The way to look at
one trace by hand before writing a reader against it.

    python3 benchmarks/tools/trace_dump.py <dir-or-xplane.pb> [max_names]
"""
from __future__ import annotations

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def dump(path: str, max_names: int = 25, out=sys.stdout) -> None:
    from jax.profiler import ProfileData

    from benchmarks.harness import trace_reduce

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    pd = ProfileData.from_file(path)
    print("file", path, os.path.getsize(path), "bytes", file=out)
    for plane in pd.planes:
        print("PLANE", plane.name, dict(plane.stats), file=out)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            agg = collections.defaultdict(lambda: [0, 0.0, None])
            for e in events:
                a = agg[e.name]
                a[0] += 1
                a[1] += e.duration_ns / 1e9
                if a[2] is None:
                    a[2] = (e.start_ns, e.duration_ns,
                            {k: str(v)[:120] for k, v in e.stats})
            rows = sorted(agg.items(), key=lambda kv: -kv[1][1])
            for name, (cnt, tot, first) in rows[:max_names]:
                print(f"    {tot:10.6f}s x{cnt:<6} {name[:100]!r} "
                      f"first={first}", file=out)


if __name__ == "__main__":
    dump(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25)

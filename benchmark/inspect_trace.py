#!/usr/bin/env python3
"""Look at a profiler trace by hand before writing code against it.

    python benchmark/inspect_trace.py <file.xplane.pb> [events per line]

Prints every plane and line with its event count and extent, and for each
line the event names that took most time, with the stats the first such
event carries. ``reduce.py`` was written against this view of a v5e trace.
"""

import sys


def main(argv):
    from jax.profiler import ProfileData

    path, top = argv[1], int(argv[2]) if len(argv) > 2 else 12
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r} stats={dict(list(plane.stats)[:6])}")
        for line in plane.lines:
            totals, first, n, lo, hi = {}, {}, 0, None, None
            for e in line.events:
                n += 1
                totals[e.name] = totals.get(e.name, 0.0) + e.duration_ns
                first.setdefault(e.name, e)
                lo = e.start_ns if lo is None else min(lo, e.start_ns)
                hi = e.end_ns if hi is None else max(hi, e.end_ns)
            if not n:
                continue
            print(f"  LINE {line.name!r}: {n} events, {(hi - lo) / 1e9:.4f} s, "
                  f"{len(totals)} names")
            for name, ns in sorted(totals.items(), key=lambda kv: -kv[1])[:top]:
                stats = {k: str(v)[:80] for k, v in list(first[name].stats)[:8]}
                print(f"    {ns / 1e6:10.3f} ms  {name[:100]!r}  {stats}")


if __name__ == "__main__":
    main(sys.argv)

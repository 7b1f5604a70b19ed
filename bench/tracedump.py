#!/usr/bin/env python3
"""Prints what a profiler trace holds, for a look by hand: each plane, its
lines with their event counts and time spans, and per device line the
events that took most time, with the statistics of one of each.

  python3 bench/tracedump.py path/to/file.xplane.pb [--top 25]
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(args.path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                print(f"  line {line.name!r}: 0 events")
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events, "
                  f"{t0 * 1e-9:.6f} .. {t1 * 1e-9:.6f} s")
            if not plane.name.startswith("/device") and not any(
                    e.name.startswith("bench.") for e in evs):
                continue
            tot: dict = {}
            for e in evs:
                n, d, _ = tot.get(e.name, (0, 0.0, e))
                tot[e.name] = (n + 1, d + e.duration_ns * 1e-9, e)
            for name, (n, d, e) in sorted(tot.items(),
                                          key=lambda kv: -kv[1][1])[
                                              : args.top]:
                stats = {k: v for k, v in e.stats}
                print(f"    {d:12.6f} s {n:7d}x  {name[:90]!r}  "
                      f"{str(stats)[:240]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

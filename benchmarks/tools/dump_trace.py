"""Look at one trace by hand before trusting the reduction: planes, their
lines, how many events each has, and the names that take most time.

    python3 benchmarks/tools/dump_trace.py <file.xplane.pb> [--fixture out.json]

``--fixture`` also writes the trace in the plain form ``benchmarks/lib/
xplane.py`` reduces (device operation lines and the benchmark's anchor),
cut to the first ``--events`` operations of each device, as a test fixture.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax
    from benchmarks.lib import xplane

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--fixture", default=None)
    ap.add_argument("--events", type=int, default=400)
    args = ap.parse_args()
    for plane in jax.profiler.ProfileData.from_file(args.path).planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            by_name = {}
            for e in events:
                by_name[e.name] = by_name.get(e.name, 0.0) + e.duration_ns
            top = sorted(by_name.items(), key=lambda kv: -kv[1])[:8]
            span = (min((e.start_ns for e in events), default=0),
                    max((e.start_ns + e.duration_ns for e in events),
                        default=0))
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{len(by_name)} names, from {span[0]:.0f} to "
                  f"{span[1]:.0f} ns")
            for name, ns in top:
                print(f"      {ns / 1e6:10.3f} ms  {name[:100]}")
    if args.fixture:
        planes = xplane.load(args.path, keep_lines=(xplane.OPS_LINE,))
        for plane in planes:
            for line in plane["lines"]:
                line["events"] = sorted(
                    line["events"], key=lambda e: e[1])[:args.events]
        with open(args.fixture, "w") as f:
            json.dump(planes, f)
        print(f"fixture written: {args.fixture} "
              f"({os.path.getsize(args.fixture)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

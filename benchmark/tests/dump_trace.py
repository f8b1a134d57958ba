#!/usr/bin/env python3
"""Print what a profiler trace holds: every plane and line with its event
count and a few events with their stats. For finding which line carries
the device operations when a new JAX names them differently.

    python3 benchmark/tests/dump_trace.py <trace-dir>"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(trace_dir: str) -> int:
    from jax.profiler import ProfileData
    from harness import xplane
    path = xplane.find_xplane(trace_dir)
    if path is None:
        print(f"no trace under {trace_dir}", file=sys.stderr)
        return 1
    print(path, os.path.getsize(path))
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            mid = len(events) // 2
            for e in events[:3] + events[mid:mid + 2]:
                print("     ", e.name, e.start_ns, e.duration_ns,
                      [(k, str(v)[:80]) for k, v in e.stats][:12])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

"""Print what a trace holds: planes, lines, and sample events with every stat.
For looking at one trace by hand before trusting the reduction.

    python3 benchmark/tests/dump_trace.py <trace_dir or .xplane.pb> [substring]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jax.profiler import ProfileData  # noqa: E402

import trace_reduce  # noqa: E402

path = sys.argv[1]
if os.path.isdir(path):
    path = trace_reduce.find_xplane(path)
needle = sys.argv[2] if len(sys.argv) > 2 else None
for plane in ProfileData.from_file(path).planes:
    print("PLANE", plane.name)
    for line in plane.lines:
        events = list(line.events)
        print("  LINE", line.name, len(events))
        shown = 0
        for ev in events:
            if needle is not None and needle not in ev.name:
                continue
            print("    EVENT", ev.name[:160], ev.start_ns, ev.duration_ns)
            for k, v in ev.stats:
                print("       ", k, "=", str(v)[:300])
            shown += 1
            if shown >= 3:
                break

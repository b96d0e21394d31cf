"""Print a trace's planes, lines and first events, for a look by hand
before trusting ``trace_reduce`` on a new chip or a new jax:

    python perf/tools/dump_trace.py <file.xplane.pb[.gz]> [events per line]
"""

from __future__ import annotations

import os
import sys


def main(argv) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import trace_reduce

    show = int(argv[2]) if len(argv) > 2 else 8
    data = trace_reduce.open_profile(argv[1])
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:show]:
                stats = ", ".join(f"{k}={v}" for k, v in list(ev.stats)[:6])
                print(f"    {ev.name!r} start={ev.start_ns:.0f}ns dur={ev.duration_ns:.0f}ns {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

#!/usr/bin/env python3
"""What a profiler trace holds: planes, lines, event counts and the first
events of each line with their stats. For looking at a trace by hand
before trusting ``trace_reduce.py`` on it, say after a jax upgrade:

    python benchmark/rehearse/describe_trace.py \
        .bench_out/trace/<cell>/plugins/profile/*/*.xplane.pb [events]
"""

import sys

from jax.profiler import ProfileData


def describe(path, events=3):
    rows = []
    for plane in ProfileData.from_file(str(path)).planes:
        rows.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            rows.append(f"  line {line.name!r}: {len(evs)} events")
            for e in evs[:events]:
                stats = {k: (v if not isinstance(v, str) else v[:80])
                         for k, v in e.stats}
                rows.append(f"    {e.name[:100]!r} start={e.start_ns} "
                            f"dur={e.duration_ns} {stats}")
    return "\n".join(rows)


if __name__ == "__main__":
    print(describe(sys.argv[1], *map(int, sys.argv[2:3])))

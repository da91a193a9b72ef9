"""Print the outline of a ``jax.profiler`` trace: planes, their lines,
how many events each holds, the stats the events carry, and the
heaviest event names. Look at one trace by hand before trusting
``benchlib/trace.py`` on a new chip or a new program.

    python3 benchmark/tools/trace_outline.py <trace dir or .xplane.pb>
"""

from __future__ import annotations

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from jax.profiler import ProfileData

    from benchlib import trace

    path = sys.argv[1]
    if os.path.isdir(path):
        path = trace.newest_trace_file(path)
    print("file", path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            t0 = min(e.start_ns for e in events)
            t1 = max(e.end_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{t0:.0f}..{t1:.0f} ns")
            total = collections.Counter()
            stats_seen = collections.Counter()
            sample = {}
            for e in events:
                total[e.name] += e.duration_ns
                st = dict(e.stats)
                stats_seen.update(st.keys())
                sample.setdefault(e.name, st)
            print("    stats:", dict(stats_seen.most_common(14)))
            for name, ns in total.most_common(12):
                st = {k: str(v)[:60] for k, v in sample[name].items()
                      if k in ("hlo_category", "tf_op", "hlo_op",
                               "hlo_module", "long_name")}
                print(f"    {ns / 1e6:10.3f} ms  {name[:70]}  {st}")


if __name__ == "__main__":
    main()

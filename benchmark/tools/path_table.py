"""Print ``benchlib/path_trace.py``'s reduction of one traced run: device
milliseconds a step by the whole path of scopes above an operation
(``blocks/h_1/hc_attn/maps``), heaviest first, and summed with the layer
index taken out (``blocks/h_*/hc_attn/maps``). An optional second
argument keeps the paths that contain it.

    python3 benchmark/tools/path_table.py <run directory> [hc_]
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from benchlib import path_trace, trace

    run_dir = sys.argv[1]
    keep = sys.argv[2] if len(sys.argv) > 2 else ""
    with open(os.path.join(run_dir, "worker.json")) as f:
        facts = json.load(f)
    steps = ((facts["trace_to"] - facts["trace_from"])
             * facts["steps_per_dispatch"])
    got = path_trace.reduce_file(
        trace.newest_trace_file(os.path.join(run_dir, "trace")), steps)
    by_kind: dict[str, float] = {}
    by_path = {}
    for path, seconds in got["under_s"].items():
        if keep not in path:
            continue
        by_path[path] = seconds / steps * 1e3
        kind = re.sub(r"\bh_\d+\b", "h_*", path)
        by_kind[kind] = by_kind.get(kind, 0.0) + seconds / steps * 1e3

    def heaviest(d: dict) -> dict:
        return {k: round(v, 4) for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])}

    print(json.dumps({"steps": steps, "sum_ms": sum(by_path.values()),
                      "by_kind_ms": heaviest(by_kind),
                      "by_path_ms": heaviest(by_path)}, indent=1))


if __name__ == "__main__":
    main()

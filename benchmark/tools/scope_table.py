"""Print what ``benchlib/program_trace.py`` reads from one traced run
beside what ``benchlib/trace.py`` reads: device milliseconds a step by
program scope (``embed`` included, which no metric reports alone) and by
opcode class, the host's ``train.*`` spans, and the idle gaps by the span
over them. The two splits are of the same self times, so their sums agree.

    python3 benchmark/tools/scope_table.py <run directory>
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from benchlib import program_trace, trace

    run_dir = sys.argv[1]
    with open(os.path.join(run_dir, "worker.json")) as f:
        facts = json.load(f)
    steps = ((facts["trace_to"] - facts["trace_from"])
             * facts["steps_per_dispatch"])
    path = trace.newest_trace_file(os.path.join(run_dir, "trace"))
    by_class = trace.reduce_trace(os.path.join(run_dir, "trace"), steps)
    by_scope = program_trace.reduce_file(path, steps)

    def per_step(d: dict) -> dict:
        return {k: round(v / steps * 1e3, 4) for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])}

    out = {"steps": steps, "class_ms": per_step(by_class["class_s"]),
           "class_sum_ms": sum(by_class["class_s"].values()) / steps * 1e3}
    if by_scope is not None:
        out.update(
            scope_ms=per_step(by_scope["scope_s"]),
            scope_sum_ms=sum(by_scope["scope_s"].values()) / steps * 1e3,
            scoped_through_a_user_ms=by_scope["inherited_s"] / steps * 1e3,
            blocks_ms=per_step(by_scope["blocks_s"]),
            host_ms=per_step(by_scope["host_s"]),
            idle_by_train_span_ms=per_step(by_scope["idle_by_span_s"]))
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()

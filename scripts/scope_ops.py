"""The device's operations beneath one scope path of a traced benchmark
run, a step: what ``benchmark/tools/path_table.py`` sums by path, split
by operation kind (``mxu``, ``kernel``, ``other``:
``benchlib/trace.py::classify``) and, with ``--ops``, listed one by one
with their calls a step and their result shapes. It is how a scope's
milliseconds are split between its matmuls, its custom calls and its
fusions (``kda/out_gate``, PR 58).

    python3 scripts/scope_ops.py <run directory> kda/out_gate [--ops 12]
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
CHECKPOINTS = ("checkpoint", "rematted_computation")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("run_dir")
    ap.add_argument("scope", help="part of a scope path, e.g. kda/out_gate")
    ap.add_argument("--ops", type=int, default=0,
                    help="list the heaviest operations too")
    args = ap.parse_args()

    from jax.profiler import ProfileData

    from benchlib import program_trace, trace

    with open(os.path.join(args.run_dir, "worker.json")) as f:
        facts = json.load(f)
    steps = ((facts["trace_to"] - facts["trace_from"])
             * facts["steps_per_dispatch"])
    with open(trace.newest_trace_file(
            os.path.join(args.run_dir, "trace")), "rb") as f:
        raw = f.read()
    profile = ProfileData.from_serialized_xspace(raw)
    names = program_trace.op_names(raw)
    (w0, w1), _ = trace._host_spans(profile)
    planes = [p for p in profile.planes if trace.DEVICE_PLANE.match(p.name)]
    by_kind = collections.Counter()
    by_path_kind = collections.Counter()
    ops = collections.defaultdict(lambda: [0.0, 0, ""])
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        modules = program_trace._clipped(lines["XLA Modules"], w0, w1)
        events = program_trace._clipped(lines["XLA Ops"], w0, w1)
        trace.self_times(events)
        for e in events:
            module = next((m["text"] for m in modules
                           if m["start"] <= e["start"] < m["end"]), "")
            name, opcode, fusion = trace.parse_hlo(e["text"])
            op_name = names.get(module, {}).get(name, ("", False))[0]
            top, below = program_trace.scope_of(op_name)
            path = "/".join((top, *below[:-1]))
            # the checkpoints' own names stand between a module and its
            # scopes (``kda/checkpoint/out_gate/checkpoint``)
            if args.scope not in "/".join(
                    p for p in path.split("/") if p not in CHECKPOINTS):
                continue
            ms = e["self_ns"] / len(planes) / steps / 1e6
            kind = trace.classify(name, opcode, fusion)
            path = re.sub(r"\bh_\d+\b", "h_*", path)
            by_kind[kind] += ms
            by_path_kind[f"{path} [{kind}]"] += ms
            # one name a layer's copies share: the instruction's, less
            # its number
            op = ops[(re.sub(r"[.\d]+$", "", name), kind, path)]
            op[0] += ms
            op[1] += 1
            op[2] = e["text"].partition(" = ")[2][:160]

    def heaviest(d) -> dict:
        return {k: round(v, 4) for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])}

    out = {"steps": steps, "sum_ms": round(sum(by_kind.values()), 4),
           "by_kind_ms": heaviest(by_kind),
           "by_path_kind_ms": heaviest(by_path_kind)}
    if args.ops:
        out["ops"] = [
            {"op": name, "kind": kind, "path": path, "ms": round(ms, 4),
             "calls_a_step": round(calls / len(planes) / steps, 2),
             "hlo": hlo}
            for (name, kind, path), (ms, calls, hlo) in sorted(
                ops.items(), key=lambda kv: -kv[1][0])[:args.ops]]
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()

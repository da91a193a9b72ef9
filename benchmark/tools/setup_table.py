"""Print a run's set-up as one timeline, from the files the run left:
``result.json`` (``driver.t_start``, the first line of ``run.py``),
``experiments/fit/fit_trace.json`` (the program's spans, driver's and
worker's, from ``core.init`` on) and ``worker.json`` (the stamp that opens
the window; the loop's own ``state_init_s`` and ``first_batch_s`` as the
cross-check from outside). Seconds, on the one monotonic clock, counted from
``t_start``.

    python3 benchmark/tools/setup_table.py <run directory>

Four tables: every span (start, seconds, span, process; a compile with its
``fun_name``, a cache load with ``compiled_in_s``, what the compile took when
the entry was written); the compiles by ``fun_name`` and kind; from
``train.fit``'s start to the first report, the seconds under each span and
the stretches under none (with the phases of ``benchlib/loop.py`` each
touches, from ``progress.jsonl``); and the two sides of the identity of
``benchlib/setup_trace.py``.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHORT_S = 0.05      # compiles and gaps under this are summed, not listed


def _load(run_dir: str, *path: str):
    with open(os.path.join(run_dir, *path)) as f:
        return json.load(f)


def _phases(run_dir: str) -> list[tuple[float, str]]:
    path = os.path.join(run_dir, "progress.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [(r["t"], r["phase"]) for r in rows]


def _phases_over(phases: list[tuple[float, str]], lo: float,
                 hi: float) -> str:
    """The phases of the loop that [lo, hi] touches, in order."""
    names = ["before the loop"]
    for at, phase in phases:
        if at <= lo:
            names = [phase]
        elif at < hi:
            names.append(phase)
    return " > ".join(names)


def main() -> None:
    from benchlib import setup_trace as st

    run_dir = sys.argv[1]
    t_start = _load(run_dir, "result.json")["driver"]["t_start"]
    worker = _load(run_dir, "worker.json")
    t_open = worker["stamps"][worker["open_i"]]
    recs = sorted(
        st.records_from_chrome(
            _load(run_dir, "experiments", "fit", "fit_trace.json")),
        key=lambda r: (r["start"], -r["end"]))
    phases = _phases(run_dir)

    print(f"{'start':>9} {'seconds':>9}  span")
    short: dict[str, list[float]] = {}
    for r in recs:
        seconds, a = r["end"] - r["start"], r["attributes"]
        if r["name"] == "train.compile" and seconds < SHORT_S:
            short.setdefault(a["kind"], []).append(seconds)
            continue
        detail = [f"{k}={a[k]}" for k in (
            "fun_name", "cache", "rank", "workers", "platform", "devices",
            "address", "nodes", "built", "bundles", "strategy")
            if a.get(k) not in (None, "")]
        detail += [f"{k}={a[k]:.3f}" for k in (
            "compiled_in_s", "first_report_s", "source_s", "place_s",
            "stall_s") if k in a]
        print(f"{r['start'] - t_start:9.3f} {seconds:9.3f}  {st.label(r)}"
              f"  [{r['process']}] {' '.join(detail)}")
    for kind, xs in sorted(short.items()):
        print(f"{'':>9} {sum(xs):9.3f}  train.compile:{kind} x {len(xs)} "
              f"under {SHORT_S} s each")

    print("\ncompiles by fun_name and kind "
          "(n, seconds, compiled_in_s of the loads)")
    by: dict[tuple[str, str], list[float]] = {}
    for r in recs:
        if r["name"] == "train.compile":
            a = r["attributes"]
            row = by.setdefault((a.get("fun_name", ""), a["kind"]),
                                [0, 0.0, 0.0])
            row[0] += 1
            row[1] += r["end"] - r["start"]
            row[2] += a.get("compiled_in_s", 0.0)
    for (fun, kind), (n, seconds, was) in sorted(
            by.items(), key=lambda kv: -kv[1][1]):
        if seconds >= SHORT_S:
            print(f"{seconds:9.3f}  {n:3d} x {kind:10s} {fun}"
                  + (f"  compiled_in_s {was:.3f}"
                     if kind == "cache_load" else ""))

    parts = st.cut(recs, t_start, t_open)
    if parts is None or "unnamed_s" not in parts:
        print("\nno train.fit span with a first report: a program from "
              "before the set-up spans")
        return
    a, b = parts["t_fit"], parts["t_first_report"]
    print(f"\nfrom train.fit's start ({a - t_start:.3f}) to the first report "
          f"({b - t_start:.3f}): seconds under each span, innermost first")
    for name, seconds in sorted(st.named_by_label(recs, a, b).items(),
                                key=lambda kv: -kv[1]):
        print(f"{seconds:9.3f}  {name}")
    print("under no span (start, seconds, the loop's phases it touches):")
    rest = 0.0
    for lo, hi in st.gaps(recs, a, b):
        if hi - lo < SHORT_S:
            rest += hi - lo
            continue
        print(f"{lo - t_start:9.3f} {hi - lo:9.3f}  "
              f"{_phases_over(phases, lo, hi)}")
    print(f"{'':>9} {rest:9.3f}  in stretches under {SHORT_S} s")
    print("from outside, worker.json: " + ", ".join(
        f"{k} {worker[k]:.3f}" for k in (
            "backend_init_s", "state_init_s", "first_batch_s")
        if k in worker))

    total = (parts["before_fit_s"] + parts["named_s"] + parts["unnamed_s"]
             + parts["warmup_s"])
    print(f"\nsetup_s {parts['setup_s']:.6f} = entry.before_fit_s "
          f"{parts['before_fit_s']:.6f} + named {parts['named_s']:.6f} + "
          f"fit.setup_unnamed_s {parts['unnamed_s']:.6f} + step.warmup_s "
          f"{parts['warmup_s']:.6f} = {total:.6f} "
          f"(off by {abs(total - parts['setup_s']):.2e})")


if __name__ == "__main__":
    main()

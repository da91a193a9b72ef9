"""Run one cell several times, each run a process of its own, and
print each metric's spread the way the driver reads it.

    python3 benchmark/tools/repeat.py --workload <cell> --seeds 11,12,13 \
        [--seconds S] [--trace 0|1] [--out DIR] [--label NAME]

A set is one call of this tool; its spread is the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a
share of the median. The lines of the runs go to ``<out>/<label>.jsonl``.
This process never imports jax: each run holds the chip alone.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", default=None)
    ap.add_argument("--label", default="set")
    ap.add_argument("--stop-on-failure", action="store_true",
                    help="make no further run after one that failed or "
                         "was not correct")
    args = ap.parse_args()
    out = os.path.abspath(args.out or os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "bench_out"))
    os.makedirs(out, exist_ok=True)
    lines = []
    for seed in args.seeds.split(","):
        cmd = [sys.executable, RUN, "--workload", args.workload, "--seed",
               seed, "--trace", args.trace, "--out", out]
        if args.seconds:
            cmd += ["--seconds", args.seconds]
        t0 = time.monotonic()
        p = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                           text=True)
        wall = time.monotonic() - t0
        last = p.stdout.strip().splitlines()[-1:] or [""]
        try:
            line = json.loads(last[0])
        except ValueError:
            line = None
        rec = {"label": args.label, "workload": args.workload,
               "seed": int(seed), "rc": p.returncode, "wall_s": wall,
               "line": line}
        if line is None or p.returncode:
            rec["stderr_tail"] = p.stderr[-4000:]
        lines.append(rec)
        with open(os.path.join(out, f"{args.label}.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        brief = line and {k: v["value"] for k, v in line["metrics"].items()}
        print(json.dumps({"seed": int(seed), "rc": p.returncode,
                          "wall_s": round(wall, 1),
                          "correct": line and line["correct"],
                          "metrics": brief}), flush=True)
        if line is None or p.returncode:
            print(p.stderr[-3000:], flush=True)
        if args.stop_on_failure and not (line and line["correct"]):
            sys.exit(1)
    good = [r["line"] for r in lines if r["line"]]
    if len(good) >= 3:
        summary = {}
        for name in good[0]["metrics"]:
            vals = [g["metrics"][name]["value"] for g in good
                    if name in g["metrics"]]
            # setup_s: the first run of a set may compile; leave it out
            if name == "setup_s":
                vals = vals[1:]
            if len(vals) >= 2 and statistics.median(vals):
                summary[name] = {"median": statistics.median(vals),
                                 "min": min(vals), "max": max(vals),
                                 "spread": spread(vals), "n": len(vals)}
        print(json.dumps({"label": args.label, "summary": summary}),
              flush=True)


if __name__ == "__main__":
    main()

"""From a finished run's facts to the result line.

End-to-end metrics come from the window's stamps (``intervals.py``);
each per-layer metric from its own reader, ``layer_metrics/<name>.py``,
given a :class:`Run`. A reader that finds nothing to read returns None
and the metric is left out of the line.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass

from benchlib import intervals, manifest, peaks, trace


@dataclass
class Run:
    """What a per-layer reader may look at."""
    cell: dict          # the manifest's entry with config_file, traffic_file
    worker: dict        # loop.py's facts (worker.json)
    driver: dict        # run.py's own times
    window: dict        # intervals.summarize of the measured window
    trace: dict | None  # trace.reduce_trace of the traced window, or None

    def window_host_ms_per_step(self, key: str) -> float:
        """Mean host milliseconds per step spent in one of the loop's
        spans (input, dispatch, sync, report) over the window."""
        o, c = self.worker["open_i"], self.worker["close_i"]
        xs = self.worker["host_s"][key][o + 1:c + 1]
        return sum(xs) / len(xs) / self.worker["steps_per_dispatch"] * 1e3

    def trace_ms_per_step(self, seconds: float) -> float:
        return seconds / self.trace["steps"] * 1e3

    def peak(self, what: str) -> float:
        return peaks.peak(self.worker["kind"], what)


def window_summary(facts: dict, chips: int) -> dict:
    o, c = facts["open_i"], facts["close_i"]
    return intervals.summarize(facts["stamps"][o:c + 1],
                               facts["steps_per_dispatch"],
                               facts["samples_per_step"], chips)


def end_to_end(man: dict, cell: dict, facts: dict, driver: dict,
               window: dict) -> dict:
    values = {
        "setup_s": facts["stamps"][facts["open_i"]] - driver["t_start"],
        "step_ms_p90": window["step_ms_p90"],
        f"{cell['config_file']['sample_unit']}_per_s_per_chip":
            window["rate_per_chip"],
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in manifest.metrics_of(man, "end_to_end", cell["name"])}


def per_layer(man: dict, run: Run, bench_dir: str) -> dict:
    out = {}
    for m in manifest.metrics_of(man, "per_layer", run.cell["name"]):
        value = manifest.load_reader(m["name"], bench_dir)(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(man: dict, cell: dict, facts: dict, driver: dict,
                run_dir: str, traced: bool, correct: bool,
                rehearsal: bool) -> dict:
    chips = cell["chips"]
    window = window_summary(facts, chips)
    device = {"platform": facts["platform"], "kind": facts["kind"],
              "count": chips if rehearsal else facts["count"],
              # memory_stats() does not see a program's temporaries
              # (PERF.md); the compiler's figure for the step does.
              "memory_peak_bytes": max(
                  facts["memory_stats_peak_bytes"],
                  facts["program_bytes"].get("total", 0))}
    line = {"correct": correct, "attempted": window["steps"],
            "failed": sum(not math.isfinite(x) for x in facts["losses"]),
            "device": device}
    group = "per_layer" if traced else "end_to_end"
    if rehearsal:       # a CPU run writes no device metric
        line["metrics"] = {
            m["name"]: {"value": None, "unit": m["unit"]}
            for m in manifest.metrics_of(man, group, cell["name"])}
    elif traced:
        steps = ((facts["trace_to"] - facts["trace_from"])
                 * facts["steps_per_dispatch"])
        reduced = trace.reduce_trace(os.path.join(run_dir, "trace"), steps)
        if reduced is None:
            raise RuntimeError("--trace 1 and no trace was written")
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
        line["metrics"] = per_layer(
            man, Run(cell, facts, driver, window, reduced),
            manifest.BENCH_DIR)
    else:
        line["metrics"] = end_to_end(man, cell, facts, driver, window)
    return line

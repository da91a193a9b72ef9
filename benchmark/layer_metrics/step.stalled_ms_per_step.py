"""step: milliseconds a step of the window that went to stalled steps, by
the program's own account: the excess_s (a report-to-report interval less
the median of those before it) of the fit's train.stall spans over the
window's steps (a dispatch of K fused steps is one report and K steps).
What step.stall_pct measures from outside (stall_pct ~ this over the mean
step), from inside. A span belongs to the interval that ends at the last
stamp at or before its end (report() follows the loop's stamp by
microseconds, on one clock) and after its start; the window's intervals end
at stamps open_i + 1 to close_i, so a stall that ends on the opening stamp
is outside. 0.0 in a window with no stall; None where the fit's
train.worker.loop has no stalls attribute (a program from before the span).
Moves step_ms_p90."""

import bisect


def read(run, of=lambda a: a["excess_s"]):
    """``of``: what one stall's attributes count for (the two readers
    beside this one hand in theirs)."""
    from benchlib import program_trace
    spans = program_trace.fit_spans() or ()
    if not any(s.name == "train.worker.loop" and "stalls" in s.attributes
               for s in spans):
        return None
    stamps = run.worker["stamps"]
    first, last = run.worker["open_i"] + 1, run.worker["close_i"]
    seconds = 0.0
    for s in spans:
        if s.name != "train.stall":
            continue
        i = bisect.bisect_right(stamps, s.mono_end) - 1
        if first <= i <= last and stamps[i] > s.mono_start:
            seconds += of(s.attributes)
    return seconds / run.window["steps"] * 1e3

"""step: of step.stalled_ms_per_step, the milliseconds a step of the
window's train.stall spans whose cause is unnamed: neither the watch
thread's beats, nor the program's counters (gc, compile, input, report),
nor the loop thread's stack said where the interval went. What the
measurement still cannot see, as a number. Moves step_ms_p90."""


def read(run):
    from benchlib import manifest
    return manifest.load_reader("step.stalled_ms_per_step")(
        run, lambda a: a["excess_s"] if a["cause"] == "unnamed" else 0.0)

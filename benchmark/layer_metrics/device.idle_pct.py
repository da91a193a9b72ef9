"""device: share of the traced window in which no operation ran on the
device, mean over chips. Moves step_ms_p90."""


def read(run):
    if run.trace is None:
        return None
    return (1.0 - run.trace["busy_s"] / run.trace["window_s"]) * 100.0

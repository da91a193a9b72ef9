"""model: device self time per step in convolutions, dots and the fusions
rooted in them. Moves step_ms_p90."""


def read(run):
    if run.trace is None:
        return None
    return run.trace_ms_per_step(run.trace["class_s"]["mxu"])

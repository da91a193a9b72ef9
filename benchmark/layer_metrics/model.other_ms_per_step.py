"""model: device self time per step in every operation that is not a matmul,
a kernel or a collective. Moves step_ms_p90."""


def read(run):
    if run.trace is None:
        return None
    return run.trace_ms_per_step(run.trace["class_s"]["other"])

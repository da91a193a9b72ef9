"""input: the part of the wait for input during which the device ran nothing:
idle gaps of the traced window that fall under the bench.input span, per
step. Moves step_ms_p90."""


def read(run):
    if run.trace is None:
        return None
    return run.trace_ms_per_step(
        run.trace["idle_by_span_s"].get("bench.input", 0.0))

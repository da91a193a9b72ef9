"""step: device self time per step under the program scope ``optimizer``
(update, apply and the gradient norm). Moves step_ms_p90."""


def read(run):
    from benchlib import program_trace
    return program_trace.scope_ms_per_step(run, "optimizer")

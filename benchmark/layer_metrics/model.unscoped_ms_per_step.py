"""model: device self time per step under none of the program scopes
``embed``, ``blocks``, ``loss`` and ``optimizer``: what the scope metrics
cannot attribute. Moves step_ms_p90."""


def read(run):
    from benchlib import program_trace
    return program_trace.scope_ms_per_step(run, "unscoped")

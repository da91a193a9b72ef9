"""model: device self time per step under the program scope ``loss`` (LM head
or classifier and the cross-entropy, both passes, and on four chips the
collectives inside them). Moves step_ms_p90."""


def read(run):
    from benchlib import program_trace
    return program_trace.scope_ms_per_step(run, "loss")

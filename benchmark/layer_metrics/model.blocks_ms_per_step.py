"""model: device self time per step under the program scope ``blocks`` (the
transformer blocks or ResNet stages and the final norm, forward and backward,
kernels included), from each operation's op_name. Moves step_ms_p90."""


def read(run):
    from benchlib import program_trace
    return program_trace.scope_ms_per_step(run, "blocks")

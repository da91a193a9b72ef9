"""model: device self time per step under the ``mlp`` modules within the
scope ``blocks``, both passes. Moves step_ms_p90."""


def read(run):
    from benchlib import program_trace
    return program_trace.scope_ms_per_step(run, "blocks", within="mlp")

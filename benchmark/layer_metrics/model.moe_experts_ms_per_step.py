"""model: device self time per step under the routed layer's ``experts``
scope (the grouped matmuls, the weights' casts and the activation), both
passes. Moves step_ms_p90."""


def read(run):
    from benchlib import moe_trace
    return moe_trace.mlp_ms_per_step(run, ("experts",))

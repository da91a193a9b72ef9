"""model: device self time per step under the expert layers' ``shared``
scope (the shared expert: a dense relu^2 MLP on every token, both passes),
beside the routed experts' ``model.moe_experts_ms_per_step``. Moves
step_ms_p90."""


def read(run):
    from benchlib import scope_trace
    return scope_trace.ms_per_step(run, "mlp", "shared")

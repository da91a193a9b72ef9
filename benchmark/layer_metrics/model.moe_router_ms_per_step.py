"""model: device self time per step under the routed layer's ``router``
scope alone (a router that is not one matrix: the state's projection, the
depth averaging, the three-layer MLP, softmax and arg-max, and the count of
routes an expert), both passes: what ``model.moe_route_ms_per_step`` lumps
with the sort and the combine. Moves step_ms_p90."""


def read(run):
    from benchlib import scope_trace
    return scope_trace.ms_per_step(run, "mlp", "router")

"""model: device self time per step under the routed layer's ``router``,
``dispatch`` and ``combine`` scopes (logits, softmax, top-k and the two
losses; sort, permutation, gather; un-sort, weighting, sum), both passes:
what routing costs beside the experts' matmuls. Moves step_ms_p90."""


def read(run):
    from benchlib import moe_trace
    return moe_trace.mlp_ms_per_step(run, ("router", "dispatch", "combine"))

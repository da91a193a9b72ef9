"""model: device self time per step under the ``maps`` scopes of ``hc_attn``
and ``hc_mlp`` alone: the float32 norm over the state's lanes, the product
with ``phi``, the two sigmoids, the clamp and the 20 Sinkhorn normalisations
with their backward, every sub-layer's. What is left of
model.hc_ms_per_step is the two mixes. Moves step_ms_p90."""


def read(run):
    from benchlib import hc_trace
    return hc_trace.ms_per_step(run, "maps")

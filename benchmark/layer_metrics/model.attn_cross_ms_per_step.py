"""model: device self time per step under ``attn/cross``, both passes: the
attention cores of the cross-decoder's ``X`` layers
(``ray_tpu/models/phi4flash.py``), the causal flash kernels over the whole row
with this layer's queries against the ``K, V`` of the one full layer. Moves
step_ms_p90."""


def read(run):
    from benchlib import scope_trace
    return scope_trace.ms_per_step(run, "attn", "cross")

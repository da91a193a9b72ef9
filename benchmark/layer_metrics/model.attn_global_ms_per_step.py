"""model: device self time per step under ``attn/core``, both passes, in a
stack that keeps its windowed layers' cores apart under ``attn/window``
(``ray_tpu/models/smallthinker.py``): the attention cores of the global
layers, the causal flash kernels over the whole row. Moves step_ms_p90."""


def read(run):
    from benchlib import scope_trace
    return scope_trace.ms_per_step(run, "attn", "core")

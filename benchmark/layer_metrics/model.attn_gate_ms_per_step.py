"""model: device self time per step under ``attn/gate``, both passes: the
head-wise output gate of ``ray_tpu/models/laguna.py`` (``W_g`` 2048 -> H_l,
the sigmoid, and the product with the core's output, 6,144 or 8,192 lanes a
token, before ``W_o``), in every layer. None for a step without the scope.
Moves step_ms_p90."""


def read(run):
    from benchlib import scope_trace
    return scope_trace.ms_per_step(run, "attn", "gate")

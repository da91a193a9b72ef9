"""model: device self time per step under ``attn/repeat``, both passes: the
copies of K and V that grouped-query layers write in front of the
equal-width flash kernels (``ray_tpu/models/laguna.py``: 6 a key/value head
in a full layer, 8 in a sliding one; ``models/smallthinker.py``: 7), and the
sum of their cotangents over the copies in the backward pass. What
GQA-native K/V in the kernel (ROADMAP B2) would take away. None for a step
without the scope. Moves step_ms_p90."""


def read(run):
    from benchlib import scope_trace
    return scope_trace.ms_per_step(run, "attn", "repeat")

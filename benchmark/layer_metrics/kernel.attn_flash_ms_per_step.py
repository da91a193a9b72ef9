"""kernel: device time per step in the custom calls under the ``attn``
modules (the Pallas flash-attention kernels, forward and backward), for a
step whose routed experts are custom calls too. Moves
tokens_per_s_per_chip."""


def read(run):
    from benchlib import moe_trace
    return moe_trace.kernel_ms_per_step(run, "attn")

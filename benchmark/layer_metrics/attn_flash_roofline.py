"""kernel: the least time the chip could take for the attention the step
needs (benchlib/flops.py::flash_attention_train_cost against the peaks
table) over the device time of the custom calls under ``attn``. Moves
tokens_per_s_per_chip."""


def read(run):
    from benchlib import moe_trace
    return moe_trace.roofline_pct(
        run, run.worker["kernel_cost_per_step"],
        moe_trace.kernel_ms_per_step(run, "attn"))

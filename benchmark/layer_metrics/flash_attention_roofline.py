"""kernel: the least time the chip could take for the attention the step
needs (benchlib/flops.py::flash_attention_train_cost against the peaks
table; compute bounds it at these shapes) over the kernels' device time.
Moves tokens_per_s_per_chip."""


def read(run):
    cost = run.worker["kernel_cost_per_step"]
    if run.trace is None or not cost or not run.trace["class_s"]["kernel"]:
        return None
    from benchlib import flops
    least = flops.roofline(cost["flops"], cost["bytes"],
                           run.peak("bf16_flops"),
                           run.peak("hbm_bytes_per_s"))["least_s"]
    per_step = run.trace["class_s"]["kernel"] / run.trace["steps"]
    return least / per_step * 100.0

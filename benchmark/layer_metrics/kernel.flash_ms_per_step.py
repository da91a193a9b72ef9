"""kernel: device time per step in custom calls (the Pallas flash-attention
kernels, forward and backward). Moves tokens_per_s_per_chip."""


def read(run):
    if run.trace is None or not run.trace["class_s"]["kernel"]:
        return None
    return run.trace_ms_per_step(run.trace["class_s"]["kernel"])

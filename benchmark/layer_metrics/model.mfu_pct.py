"""model: required forward+backward operations per sample (benchlib/flops.py)
times the window's samples per second per chip (all the work over all the
time, as the end-to-end rate), over the chip's published bf16 peak. Moves
step_ms_p90."""


def read(run):
    return (run.worker["flops_per_sample"] * run.window["rate_per_chip"]
            / run.peak("bf16_flops") * 100.0)

"""Train orchestration: host milliseconds inside train.report per step, mean
over the window. Moves step_ms_p90."""


def read(run):
    return run.window_host_ms_per_step("report")

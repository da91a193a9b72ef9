"""step: the median step interval of the window. Moves step_ms_p90."""


def read(run):
    return run.window["step_ms_p50"]

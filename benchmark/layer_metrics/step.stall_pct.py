"""step: shortfall of the end-to-end rate (all steps over all the time
between the first and last stamp) against the rate at the median step:
what stalls cost the window. Moves step_ms_p90."""


def read(run):
    return run.window["stall_pct"]

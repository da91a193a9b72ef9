"""device: share of the traced window the device was idle in gaps of 20 us
and more that no train.* span of the program covers: idle time the program's
own spans cannot name. Moves step_ms_p90."""


def read(run):
    from benchlib import program_trace
    got = program_trace.of_run(run)
    if got is None or not got["host_s"]:
        return None
    return (got["idle_by_span_s"].get("no_train_span", 0.0)
            / got["window_s"] * 100.0)

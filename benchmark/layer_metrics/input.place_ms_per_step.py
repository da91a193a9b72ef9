"""input: host milliseconds per step the prefetcher's thread spent placing a
batch on the device, from the program's train.input.place spans in the traced
window. Moves step_ms_p90."""


def read(run):
    from benchlib import program_trace
    return program_trace.host_ms_per_step(run, "train.input.place")

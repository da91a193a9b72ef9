"""input: host milliseconds per step the consuming loop waited on the
prefetcher's queue, from the program's train.input.wait spans in the traced
window (what bench.input times from outside). Moves step_ms_p90."""


def read(run):
    from benchlib import program_trace
    return program_trace.host_ms_per_step(run, "train.input.wait")

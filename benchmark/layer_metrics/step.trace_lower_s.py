"""step: seconds the worker spent tracing and lowering programs before the
window opened: the program's train.compile spans of kind trace and lower that
ended before the opening stamp (same monotonic clock). Moves setup_s."""


def read(run):
    from benchlib import program_trace
    return program_trace.compile_s(
        ("trace", "lower"), run.worker["stamps"][run.worker["open_i"]])

"""step: seconds the worker spent loading executables from the persistent
compile cache before the window opened: the program's train.compile spans of
kind cache_load. Moves setup_s."""


def read(run):
    from benchlib import program_trace
    return program_trace.compile_s(
        ("cache_load",), run.worker["stamps"][run.worker["open_i"]])

"""Train orchestration: seconds in fit()'s train.fit.start_loop span (dataset
split and the start_loop call to every worker). Moves setup_s."""


def read(run):
    from benchlib import program_trace
    return program_trace.phase_s("train.fit.start_loop")

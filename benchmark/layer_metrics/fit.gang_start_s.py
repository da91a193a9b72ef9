"""Train orchestration: seconds in fit()'s train.fit.gang_start span
(placement group, actor creation, worker boot and imports, barrier). Moves
setup_s."""


def read(run):
    from benchlib import program_trace
    return program_trace.phase_s("train.fit.gang_start")

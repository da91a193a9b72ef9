"""step: seconds from the session's first train.report() (train.worker.loop's
start plus its first_report_s) to the stamp that opens the window: the
dispatches the loop lets go by until the compile count stands still. Moves
setup_s."""


def read(run):
    from benchlib import setup_trace
    return setup_trace.part(run, "warmup_s")

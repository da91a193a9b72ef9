"""Train orchestration: of the seconds from train.fit's start to the first
report, those under no span of the program (driver's and worker's spans on the
one monotonic clock, overlaps counted once, the containers train.fit,
train.fit.poll and train.worker.loop left out): code of the user's loop that
the program cannot name. With entry.before_fit_s, the named seconds and
step.warmup_s it sums to setup_s (benchlib/setup_trace.py). Moves setup_s."""


def read(run):
    from benchlib import setup_trace
    return setup_trace.part(run, "unnamed_s")

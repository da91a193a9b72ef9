"""device boundary: seconds in a worker's train.worker.backend_init span (the
longest of the newest gang's): the program opening the accelerator backend
ahead of the user's loop. What worker.backend_init_s timed from outside before
the program had the span. Moves setup_s."""


def read(run):
    from benchlib import setup_trace
    return setup_trace.fit_span_s("train.worker.backend_init")

"""Train orchestration: seconds in a worker's train.worker.process span (the
longest of the newest gang's: workers start side by side): from the first line
of core/worker_entry.py to TrainWorker.__init__ (interpreter, imports, the dial
back, the actor's construction); the bulk of fit.gang_start_s. Moves
setup_s."""


def read(run):
    from benchlib import setup_trace
    return setup_trace.fit_span_s("train.worker.process")

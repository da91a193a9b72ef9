"""collectives: device time per step, per chip, from the start of a
collective to its end (an asynchronous pair from -start to -done).
Moves tokens_per_s_per_chip."""


def read(run):
    if run.trace is None:
        return None
    return run.trace_ms_per_step(run.trace["collective_s"])

"""collectives: the part of the collective time during which no other
operation ran on that chip. Moves tokens_per_s_per_chip."""


def read(run):
    if run.trace is None:
        return None
    return run.trace_ms_per_step(run.trace["collective_exposed_s"])

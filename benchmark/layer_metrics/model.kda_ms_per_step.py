"""model: device self time per step under the ``kda`` modules within the
scope ``blocks`` (the projections, the three convolutions, the norms, the
decay, the recurrence, the output gate and ``W_o``, both passes, the
backward pass's second run of everything between the projections and
``W_o`` included). Moves step_ms_p90."""


def read(run):
    from benchlib import kda_trace
    return kda_trace.ms_per_step(run)

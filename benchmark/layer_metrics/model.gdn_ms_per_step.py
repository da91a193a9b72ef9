"""model: device self time per step under the ``gdn`` modules within the
scope ``blocks`` (a Gated DeltaNet mixer: the two input projections, the
convolution, the decay, the recurrence, the output gate and ``W_out``,
both passes, the backward pass's second run of what a recomputed block
does not keep included). Moves step_ms_p90."""


def read(run):
    from benchlib import gdn_trace
    return gdn_trace.ms_per_step(run)

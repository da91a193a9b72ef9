"""model: device self time per step under the ``mamba`` modules within the
scope ``blocks`` (in_proj, the convolution, the scan, the gated norm,
out_proj, both passes). Moves step_ms_p90."""


def read(run):
    from benchlib import scope_trace
    return scope_trace.ms_per_step(run, "mamba")

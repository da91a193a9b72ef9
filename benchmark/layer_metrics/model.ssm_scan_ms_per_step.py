"""model: device self time per step under the Mamba-2 layers' ``scan``
scope (``ops/ssm.py::mamba2_scan``: the chunks' matmuls, the decays, the
carry over chunks, and the backward's recomputation of all of it). Moves
step_ms_p90."""


def read(run):
    from benchlib import scope_trace
    return scope_trace.ms_per_step(run, "mamba", "scan")

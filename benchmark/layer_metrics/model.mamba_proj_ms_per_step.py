"""model: device self time per step under ``mamba/in_proj`` and
``mamba/out_proj`` (``ray_tpu/models/nemotron_h.py::Mamba2Mixer``), both
passes and a recomputed block's second forward: the Mamba-2 mixers'
matmuls. With model.ssm_scan_ms_per_step it splits model.mamba_ms_per_step
three ways: these matmuls, the scan, and as the remainder the byte-bound
convolution and gated norm. None for a step without the scopes. Moves
step_ms_p90."""


def read(run):
    from benchlib import scope_trace
    found = [ms for ms in (scope_trace.ms_per_step(run, "mamba", scope)
                           for scope in ("in_proj", "out_proj"))
             if ms is not None]
    return sum(found) if found else None

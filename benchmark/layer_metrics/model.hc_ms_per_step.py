"""model: device self time per step under every ``hc_*`` scope of the
residual path (ray_tpu/ops/hyper_connections.py): ``hc_attn`` and ``hc_mlp``
beside each block's ``attn`` and ``mlp`` (their ``maps``, ``pre`` and
``post``), ``hc_expand`` and ``hc_collapse``, the MTP module's among them,
both passes, a recomputed block's second run of them included. None for a
step with one residual stream. Moves step_ms_p90."""


def read(run):
    from benchlib import hc_trace
    return hc_trace.ms_per_step(run)

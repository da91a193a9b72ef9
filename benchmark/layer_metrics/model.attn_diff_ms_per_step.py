"""model: device self time per step under ``attn/diff``, both passes: what
differential attention adds behind its cores
(``ray_tpu/ops/attention.py::differential_attention``): the layer's
``lambda`` from its four vectors, ``A1 [v1 | v2] - lambda A2 [v1 | v2]`` on
the kernels' output, the pair's 128-wide RMSNorm, its scale and ``1 -
lambda_init``. The copies that write a pair's heads out four times sit under
``attn/repeat`` and are not in it. Moves step_ms_p90."""


def read(run):
    from benchlib import scope_trace
    return scope_trace.ms_per_step(run, "attn", "diff")

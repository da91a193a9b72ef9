"""model: device self time per step under ``attn/window``, both passes: the
attention cores of the windowed layers (the flash kernels' calls under a
window: the forward kernel, the dq and the dk/dv kernel, their grids over the
band's blocks alone), as ``ray_tpu/models/smallthinker.py`` scopes them. The
key/value heads' copies in front of the kernel sit under ``attn/repeat`` and
are not in it. Moves step_ms_p90."""


def read(run):
    from benchlib import scope_trace
    return scope_trace.ms_per_step(run, "attn", "window")

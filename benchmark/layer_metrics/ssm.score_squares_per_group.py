"""kernel: how many times the Mamba-2 scan makes one group's ``C.B^T``
score square a chunk, from the note ``ssm_blocks_per_group`` that
``Mamba2Mixer`` leaves on the step's ``train.compile`` span of kind
``trace`` (``ops/ssm.py::score_squares_per_group``: once a head block of
eight heads on the kernels' path, so 8 at one group of 64 heads and 1 at
eight groups; 1 on the XLA path). What a square shared by a group's head
blocks (ROADMAP B2) would bring to 1. None for a program without the note.
Moves step_ms_p90."""


def read(run):
    from benchlib import program_trace
    for span in reversed(program_trace.fit_spans() or []):
        noted = span.attributes.get("ssm_blocks_per_group")
        if span.name == "train.compile" and noted is not None:
            return float(noted)
    return None

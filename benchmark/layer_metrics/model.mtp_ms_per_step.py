"""model: device self time per step of the multi-token-prediction module:
everything under ``blocks/mtp`` (its projection of the two normed streams
and its block's ``attn`` and ``mlp``) and under ``loss/mtp`` (its norm, its
pass over the head and its cross-entropy), both passes. Moves
step_ms_p90."""


PATHS = ("blocks/mtp", "loss/mtp")


def read(run):
    from benchlib import path_trace
    return path_trace.ms_per_step(run, PATHS)

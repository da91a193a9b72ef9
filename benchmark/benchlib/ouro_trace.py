"""Device self time of a traced run under the looped stack's own scopes
(``ray_tpu/models/ouro.py``), from ``path_trace``'s reduction.

The passes are one loop round the stack, and a recomputed block adds
parts of its own, so an operation's path reads
``blocks/while/body/checkpoint/rematted_computation/h_3/attn_post_norm``:
a path is matched by its parts, as ``hc_trace`` matches, and not by a
prefix.

Returns None where there is nothing to read: no trace, or a step without
such a scope (every program from before the model).
"""

from __future__ import annotations

from benchlib import path_trace

NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm", "norm_f")
POST_NORMS = ("attn_post_norm", "mlp_post_norm")


def ms_per_step(run, wanted, only_with=None) -> float | None:
    """Device milliseconds a step at the scope paths whose parts
    ``wanted(top, parts beneath)`` takes; None where there is none, or
    where ``only_with`` is given and takes no path of the step."""
    got = path_trace.of_run(run)
    if got is None:
        return None
    paths = {path: (path.split("/")[0], path.split("/")[1:])
             for path in got["under_s"]}
    if only_with and not any(only_with(*p) for p in paths.values()):
        return None
    found = [got["under_s"][path] for path, p in paths.items() if wanted(*p)]
    return sum(found) / got["steps"] * 1e3 if found else None


def exit_gate_ms_per_step(run) -> float | None:
    """``exit_gate`` under ``blocks`` and ``exit`` under ``loss``."""
    return ms_per_step(run, lambda top, parts: (
        (top == "blocks" and "exit_gate" in parts)
        or (top == "loss" and "exit" in parts)))


def _under_blocks(names):
    return lambda top, parts: top == "blocks" and any(
        p in names for p in parts)


def sandwich_norm_ms_per_step(run) -> float | None:
    """The four norms of every block and the final norm, in a step whose
    blocks have the post-norms (None for a two-norm stack)."""
    return ms_per_step(run, _under_blocks(NORMS),
                       only_with=_under_blocks(POST_NORMS))

"""Device self time of a traced run under the ``gdn`` modules within
``blocks``, by the scope right beneath (``qkvz``, ``ba``, ``conv``,
``qk_norm``, ``decay``, ``scan``, ``out_gate``, ``out``).

A Gated DeltaNet mixer (``ray_tpu/models/qwen3_next.py``) is laid out as
a Kimi Delta Attention mixer is, most of it inside ``jax.checkpoint``s:
this is ``kda_trace.py``'s reading of ``path_trace.py``'s reduction
under another module's name.

Returns None where there is nothing to read: no trace, no ``train.fit``
span, a step without the module (a program from before it).
"""

from __future__ import annotations

from benchlib import kda_trace, path_trace

MODULE = "gdn"


def scope_under(scopes) -> str | None:
    """The scope right beneath ``gdn`` among the scopes above an
    operation (``""`` for what sits under the module and nothing more);
    None for an operation outside the module or outside ``blocks``."""
    if scopes[0] != "blocks" or MODULE not in scopes:
        return None
    after = [p for p in scopes[scopes.index(MODULE) + 1:]
             if p not in kda_trace.WRAPPERS]
    return after[0] if after else ""


def ms_per_step(run, scope: str | None = None) -> float | None:
    """Device milliseconds a step under ``gdn`` (every scope beneath
    it), or under its one ``scope``; None where the step has no such
    module or scope."""
    got = path_trace.of_run(run)
    if got is None:
        return None
    found = [seconds for path, seconds in got["under_s"].items()
             if (at := scope_under(path.split("/"))) is not None
             and scope in (None, at)]
    return sum(found) / got["steps"] * 1e3 if found else None

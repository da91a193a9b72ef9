"""Device self time of a traced run under the ``kda`` modules within
``blocks``, by the scope right beneath (``qkv``, ``conv``, ``qk_norm``,
``decay``, ``scan``, ``out_gate``, ``out``).

A Kimi Delta Attention mixer (``ray_tpu/models/kimi_linear.py``) is a
fourth module beside ``scope_trace.py``'s three, and most of it lives
inside ``jax.checkpoint``s, whose names (``checkpoint``,
``rematted_computation``) stand between the module and its scopes in an
operation's path, and in front of ``h_i`` where the block is recomputed.
So this reads ``path_trace.py``'s reduction (self times by the whole
path of scopes) and finds the module and the scope beneath it in each
path, those names skipped: no walk of the profile of its own.

Returns None where there is nothing to read: no trace, no ``train.fit``
span, a step without the module (a program from before it).
"""

from __future__ import annotations

from benchlib import path_trace

MODULE = "kda"
WRAPPERS = ("checkpoint", "rematted_computation")


def scope_under(scopes) -> str | None:
    """The scope right beneath ``kda`` among the scopes above an
    operation (``""`` for what sits under the module and nothing more);
    None for an operation outside the module or outside ``blocks``."""
    if scopes[0] != "blocks" or MODULE not in scopes:
        return None
    after = [p for p in scopes[scopes.index(MODULE) + 1:]
             if p not in WRAPPERS]
    return after[0] if after else ""


def ms_per_step(run, scope: str | None = None) -> float | None:
    """Device milliseconds a step under ``kda`` (every scope beneath
    it), or under its one ``scope``; None where the step has no such
    module or scope."""
    got = path_trace.of_run(run)
    if got is None:
        return None
    found = [seconds for path, seconds in got["under_s"].items()
             if (at := scope_under(path.split("/"))) is not None
             and scope in (None, at)]
    return sum(found) / got["steps"] * 1e3 if found else None

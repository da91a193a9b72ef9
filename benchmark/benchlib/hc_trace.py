"""Device self time of a traced run under the residual path's scopes
(``ray_tpu/ops/hyper_connections.py``), from ``path_trace``'s reduction.

The path opens ``hc_attn`` and ``hc_mlp`` beside each block's ``attn``
and ``mlp`` (``maps``, ``pre`` and ``post`` beneath each), ``hc_expand``
under ``embed`` and ``hc_collapse`` under ``blocks``, and the MTP
module's block and its expand and collapse the same: names that no
reader of ``attn`` or ``mlp`` matches, since those match whole path
parts. A recomputed block adds parts of its own to the paths
(``checkpoint``, ``rematted_computation``), so a path is matched by its
parts and not by a prefix.

Returns None where there is nothing to read: no trace, or a step with
one residual stream (every program from before the path).
"""

from __future__ import annotations

from benchlib import path_trace


def ms_per_step(run, beneath: str | None = None) -> float | None:
    """Device milliseconds a step at scope paths with a part named
    ``hc_*`` and, given ``beneath``, a part of that name after it
    (``maps``: the maps alone)."""
    got = path_trace.of_run(run)
    if got is None:
        return None
    found = []
    for path, seconds in got["under_s"].items():
        parts = path.split("/")
        at = next((i for i, p in enumerate(parts) if p.startswith("hc_")),
                  None)
        if at is not None and (beneath is None or beneath in parts[at + 1:]):
            found.append(seconds)
    return sum(found) / got["steps"] * 1e3 if found else None

"""Device self time of a traced run by the module under ``blocks`` and
the scope right beneath it.

``program_trace.reduce_profile`` splits ``blocks`` into ``attn`` and
``mlp``, ``moe_trace`` a routed ``mlp`` into its four scopes. A stack
whose layers are of several kinds (``ray_tpu/models/nemotron_h.py``)
has a third module, ``mamba``, with ``in_proj``, ``conv``, ``scan``,
``gate_norm`` and ``out_proj`` beneath it, and a ``shared`` expert
beneath ``mlp``. This file reads the same profile once more with
``program_trace``'s and ``trace``'s own functions and keys the self
times by ``<module>/<scope beneath>`` (``mamba/scan``, ``mlp/shared``;
``<module>/`` for what sits under the module and under nothing more).

Returns None where there is nothing to read: no trace, no ``train.fit``
span, a step without the module (a program from before it).
"""

from __future__ import annotations

import functools
import os

from benchlib import program_trace, trace

MODULES = ("mamba", "attn", "mlp")


def reduce_profile(profile, names: dict, steps: int) -> dict | None:
    """``under_s``: seconds per device inside ``bench.window`` by
    ``<module>/<scope>``."""
    window, _ = trace._host_spans(profile)
    planes = [p for p in profile.planes if trace.DEVICE_PLANE.match(p.name)]
    if window is None or not planes:
        return None
    w0, w1 = window
    n = len(planes)
    under: dict[str, float] = {}
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            return None
        modules = (program_trace._clipped(lines["XLA Modules"], w0, w1)
                   if "XLA Modules" in lines else [])
        events = program_trace._clipped(lines["XLA Ops"], w0, w1)
        trace.self_times(events)
        for e in events:
            module = next((m["text"] for m in modules
                           if m["start"] <= e["start"] < m["end"]), "")
            name = trace.parse_hlo(e["text"])[0]
            op_name = names.get(module, {}).get(name, ("", False))[0]
            top, below = program_trace.scope_of(op_name)
            if top != "blocks":
                continue
            at = next((i for i, part in enumerate(below)
                       if part in MODULES), None)
            if at is None:
                continue
            key = f"{below[at]}/{below[at + 1] if at + 2 < len(below) else ''}"
            under[key] = under.get(key, 0.0) + e["self_ns"] / n / 1e9
    return {"devices": n, "steps": steps, "under_s": under}


@functools.lru_cache(maxsize=2)
def reduce_file(path: str, steps: int) -> dict | None:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    return reduce_profile(ProfileData.from_serialized_xspace(raw),
                          program_trace.op_names(raw), steps)


def of_run(run) -> dict | None:
    """The reduction of a traced run's profile, found as
    ``program_trace.of_run`` finds it."""
    spans = program_trace.fit_spans()
    if run.trace is None or spans is None:
        return None
    trial_dir = spans[0].attributes.get("trial_dir")
    if not trial_dir:
        return None
    path = trace.newest_trace_file(os.path.join(
        os.path.dirname(os.path.dirname(trial_dir)), "trace"))
    return reduce_file(path, run.trace["steps"]) if path else None


def ms_per_step(run, module: str, scope: str | None = None) -> float | None:
    """Device milliseconds a step under ``module`` (every scope beneath
    it), or under its one ``scope``; None where the step has no such
    module or scope."""
    got = of_run(run)
    if got is None:
        return None
    want = f"{module}/" if scope is None else f"{module}/{scope}"
    found = [v for k, v in got["under_s"].items()
             if (k.startswith(want) if scope is None else k == want)]
    return sum(found) / got["steps"] * 1e3 if found else None

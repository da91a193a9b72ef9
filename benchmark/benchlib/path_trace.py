"""Device self time of a traced run under any scope path: the reader
for a part of the program that no module-keyed reader sees.

``program_trace.reduce_profile`` splits the step by top-level scope,
``scope_trace`` the ``blocks`` by module (``attn``, ``mlp``, ``mamba``)
and ``moe_trace`` a routed ``mlp``; each walks the profile with its own
keys. This file walks it once more with their functions and keys the
self times by the whole path of scopes above an operation, from the
top-level scope down (``blocks/mtp/h/attn/core``, ``loss/mtp/mtp_norm``),
so that a reader names the paths it sums and a new part of a model
needs a list of prefixes, not a fifth walk. Its first user is the
multi-token-prediction module of ``ray_tpu/models/joyai.py``, which lies
across two top-level scopes: ``blocks/mtp`` (its block's ``attn`` and
``mlp`` are also counted by those readers with the other blocks') and
``loss/mtp``.

Returns None where there is nothing to read: no trace, no ``train.fit``
span, a step with no such scope (a program from before it).
"""

from __future__ import annotations

import functools
import os

from benchlib import program_trace, trace


def reduce_profile(profile, names: dict, steps: int) -> dict | None:
    """``under_s``: seconds per device inside ``bench.window`` by scope
    path, ``<top-level scope>/<every scope beneath it>``; what lies
    under no top-level scope is left out."""
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
            if top == "unscoped":
                continue
            path = "/".join((top, *below[:-1]))     # less the operation
            under[path] = under.get(path, 0.0) + e["self_ns"] / n / 1e9
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


def under(got: dict, prefixes) -> list[float]:
    """The seconds of ``got["under_s"]`` at or beneath any of the scope
    paths ``prefixes``."""
    return [v for k, v in got["under_s"].items()
            if any(k == p or k.startswith(p + "/") for p in prefixes)]


def ms_per_step(run, prefixes) -> float | None:
    """Device milliseconds a step at or beneath the scope paths
    ``prefixes`` (``("blocks/mtp", "loss/mtp")``); None where the step
    has none of them."""
    got = of_run(run)
    found = under(got, prefixes) if got else []
    return sum(found) / got["steps"] * 1e3 if found else None

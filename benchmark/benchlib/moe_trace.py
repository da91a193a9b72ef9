"""The routed-expert layer's share of a traced run.

``program_trace.reduce_profile`` splits ``blocks`` into ``attn`` and
``mlp``. A routed ``mlp`` (``ray_tpu/ops/moe.py::routed_ffn``) opens
four scopes of its own beneath that — ``router`` (logits, softmax,
top-k, the two losses), ``dispatch`` (sort, permutation, gather),
``experts`` (the grouped matmuls and the activation), ``combine``
(un-sort, weighting, sum) — and its grouped matmuls are custom calls
like the attention's kernels. This file reads the same profile once
more with ``program_trace``'s and ``trace``'s own functions and gives
device self time by those four scopes, and the custom calls' time
under ``attn`` and under ``mlp`` apart.

Returns None where there is nothing to read: no trace, no ``train.fit``
span, a step without the scopes (a program from before them).
"""

from __future__ import annotations

import functools
import os

from benchlib import program_trace, trace

MOE_SCOPES = ("router", "dispatch", "experts", "combine")


def reduce_profile(profile, names: dict, steps: int) -> dict | None:
    """Seconds per device inside ``bench.window``: ``mlp_s`` by the
    routed layer's scope (``other`` for what sits under ``mlp`` and
    none of the four), ``kernel_s`` the custom calls' self time by the
    module they sit under (``attn``, ``mlp``, ``other``)."""
    window, _ = trace._host_spans(profile)
    planes = [p for p in profile.planes if trace.DEVICE_PLANE.match(p.name)]
    if window is None or not planes:
        return None
    w0, w1 = window
    n = len(planes)
    mlp: dict[str, float] = {}
    kernel: dict[str, float] = {}
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
            name, opcode, kind = trace.parse_hlo(e["text"])
            op_name = names.get(module, {}).get(name, ("", False))[0]
            top, below = program_trace.scope_of(op_name)
            seconds = e["self_ns"] / n / 1e9
            under = next((m for m in ("attn", "mlp")
                          if top == "blocks" and m in below), "other")
            if trace.classify(name, opcode, kind) == "kernel":
                kernel[under] = kernel.get(under, 0.0) + seconds
            if under == "mlp":
                after = below[below.index("mlp") + 1:]
                sub = next((s for s in after if s in MOE_SCOPES), "other")
                mlp[sub] = mlp.get(sub, 0.0) + seconds
    return {"devices": n, "steps": steps, "mlp_s": mlp, "kernel_s": kernel}


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


def mlp_ms_per_step(run, scopes: tuple[str, ...]) -> float | None:
    """Device milliseconds a step under these scopes of the routed
    layer, both passes; None where the step has no routed layer."""
    got = of_run(run)
    if got is None or "experts" not in got["mlp_s"]:
        return None
    return sum(got["mlp_s"].get(s, 0.0) for s in scopes) / got["steps"] * 1e3


def kernel_ms_per_step(run, under: str) -> float | None:
    """Device milliseconds a step in custom calls under ``attn`` or
    ``mlp``; None where there are none."""
    got = of_run(run)
    if got is None or not got["kernel_s"].get(under):
        return None
    return got["kernel_s"][under] / got["steps"] * 1e3


def roofline_pct(run, cost: dict | None, ms: float | None) -> float | None:
    """The least time the chip could take for ``cost`` (operations and
    bytes a step, against the peaks table) as a share of ``ms`` measured
    milliseconds a step; None where either is missing."""
    if not cost or not ms:
        return None
    from benchlib import flops
    least = flops.roofline(cost["flops"], cost["bytes"],
                           run.peak("bf16_flops"),
                           run.peak("hbm_bytes_per_s"))["least_s"]
    return least / (ms / 1e3) * 100.0

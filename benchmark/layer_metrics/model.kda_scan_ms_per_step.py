"""model: device self time per step under the Kimi Delta Attention layers'
``scan`` scope (``ops/kda.py::kda_scan``: a chunk's key-key and query-key
products, the triangular solve, the walk over the chunks' states, and the
backward's recomputation of all of it). Moves step_ms_p90."""


def read(run):
    from benchlib import kda_trace
    return kda_trace.ms_per_step(run, "scan")

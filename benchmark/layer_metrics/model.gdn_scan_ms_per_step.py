"""model: device self time per step under the Gated DeltaNet layers'
``scan`` scope (``ops/kda.py::gdn_scan``: a chunk's key-key and query-key
products under its decay matrix, the triangular solve, the walk over the
chunks' states, and the backward's recomputation of a chunk's squares).
Moves step_ms_p90."""


def read(run):
    from benchlib import gdn_trace
    return gdn_trace.ms_per_step(run, "scan")

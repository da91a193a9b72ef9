"""model: device self time per step under the ``gmu`` modules within the
scope ``blocks``, both passes: the gated memory units
(``ray_tpu/models/phi4flash.py``: ``in_proj``, the gate against the last
self-decoder scan's output, ``out_proj``). ``gmu`` is no module that
``scope_trace.py`` keys, so this sums ``path_trace.py``'s reduction over the
paths that hold it, wherever a recomputed block's names put it. Moves
step_ms_p90."""


def read(run):
    from benchlib import path_trace
    got = path_trace.of_run(run)
    if got is None:
        return None
    found = [seconds for path, seconds in got["under_s"].items()
             if path.startswith("blocks/") and "gmu" in path.split("/")]
    return sum(found) / got["steps"] * 1e3 if found else None

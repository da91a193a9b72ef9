"""model: device self time per step under latent attention's ``q_down``,
``q_up``, ``kv_down``, ``kv_up`` and ``rope`` scopes within ``attn``: what
MLA costs beside its kernels (the two down projections with their norms,
the up-projections, the rotation), both passes, the backward pass's second
run of the up-projections included. Moves step_ms_p90."""

SCOPES = ("q_down", "q_up", "kv_down", "kv_up", "rope")


def read(run):
    from benchlib import scope_trace
    found = [ms for ms in (scope_trace.ms_per_step(run, "attn", s)
                           for s in SCOPES) if ms is not None]
    return sum(found) if found else None

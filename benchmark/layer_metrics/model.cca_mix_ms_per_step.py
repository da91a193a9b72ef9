"""model: device self time per step under CCA's ``conv``, ``mix`` and
``rope`` scopes within ``attn``: what lies between the compressing
projections and the flash kernel (the two causal convolutions, the q-k mean,
the L2 norm with its temperature, the partial rotation), both passes, the
backward pass's second run of them included. Moves step_ms_p90."""

SCOPES = ("conv", "mix", "rope")


def read(run):
    from benchlib import scope_trace
    found = [ms for ms in (scope_trace.ms_per_step(run, "attn", s)
                           for s in SCOPES) if ms is not None]
    return sum(found) if found else None

"""model: device self time per step of every operation whose scope path holds
a ``rematted_computation`` part, both passes' scopes alike: *all* the device
time spent making again what a forward pass already made under a
``jax.checkpoint``, whoever asked for it. That is a recomputed block's second
forward less what ``ops/attention.py::remat_policy`` keeps by name, and the
inner checkpoints too (``_kda_core``'s, the XLA scans' chunks), each operation
counted once however deep the checkpoints nest. What
``tools/path_table.py <run> rematted_computation`` sums by hand, from the same
reduction (``benchlib/path_trace.py``'s ``under_s``, so what lies under no
top-level scope is left out). With ``16.909 - device.hbm_held_gb`` beside it a
cell's row says how many milliseconds are left to buy and how many GB there
are to buy them with. **Not in the number, so the name says more than the
reader sees**: a recomputation that a ``custom_vjp``'s backward rule writes out
by hand carries no such part (latent attention's ``q_up``, ``kv_up``, ``rope``
run again on the kernels' path, ``ops/mla.py::attend_bwd``; a backward kernel
that rebuilds its block's states). The JoyAI cell therefore reads nothing and
is not listed, and the Xing4.0 cell, listed for its recomputed blocks,
under-reads by its latent attention's second projections for the same reason:
its milliseconds left to buy are the number plus those, until ``attend_bwd``
names them under a scope of their own (ROADMAP A17). None for a step that
recomputes nothing under a ``jax.checkpoint``. Moves step_ms_p90."""


def read(run):
    from benchlib import path_trace
    got = path_trace.of_run(run)
    if got is None:
        return None
    found = [seconds for path, seconds in got["under_s"].items()
             if "rematted_computation" in path.split("/")]
    return sum(found) / got["steps"] * 1e3 if found else None

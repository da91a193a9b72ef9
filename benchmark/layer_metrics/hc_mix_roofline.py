"""kernel: the least time the chip could take for the residual path
(benchlib/flops_xing.py::hc_train_cost against the peaks table: (6 n + 5) d
elements a token a sub-layer over the HBM, forward and backward, the state in
its stated type; the HBM bounds it) over model.hc_ms_per_step. The path is
XLA's fusions today; a kernel for the maps and the two mixes would raise
this. Moves tokens_per_s_per_chip."""


def read(run):
    from benchlib import hc_trace, moe_trace
    return moe_trace.roofline_pct(
        run, run.worker.get("shapes", {}).get("hc_cost_per_step"),
        hc_trace.ms_per_step(run))

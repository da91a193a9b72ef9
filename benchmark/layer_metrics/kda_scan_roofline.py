"""kernel: the least time the chip could take for the gated delta rules
the step needs (benchlib/flops_kimi_linear.py::kda_scan_train_cost against
the peaks table: the chunked form's operations at the stated chunk, and q,
k, v, g, beta, o, o's cotangent and the five gradients once each) over the
device time under the ``kda/scan`` scope. Moves tokens_per_s_per_chip."""


def read(run):
    from benchlib import kda_trace, moe_trace
    return moe_trace.roofline_pct(
        run, run.worker.get("shapes", {}).get("kda_scan_cost_per_step"),
        kda_trace.ms_per_step(run, "scan"))

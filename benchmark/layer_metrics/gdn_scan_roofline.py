"""kernel: the least time the chip could take for the gated delta rules
with a decay a head that the step needs
(benchlib/flops_qwen3_next.py::gdn_scan_train_cost against the peaks table:
the chunked form's operations at the stated chunk, the score products once
a key head; q, k and their cotangents at the key heads' width, v, o and
theirs at the value heads', g, beta and theirs a float a row a head, once
each) over the device time under the ``gdn/scan`` scope. Moves
step_ms_p90."""


def read(run):
    from benchlib import gdn_trace, moe_trace
    return moe_trace.roofline_pct(
        run, run.worker.get("shapes", {}).get("gdn_scan_cost_per_step"),
        gdn_trace.ms_per_step(run, "scan"))

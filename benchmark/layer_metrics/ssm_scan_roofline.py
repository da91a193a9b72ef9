"""kernel: the least time the chip could take for the selective scans the
step needs (benchlib/flops_nemotron.py::ssm_scan_train_cost against the
peaks table; the HBM bounds it at these shapes: 3.8 ms of bytes against
1.4 ms of operations) over the device time under the ``scan`` scope. Moves
tokens_per_s_per_chip."""


def read(run):
    from benchlib import moe_trace, scope_trace
    return moe_trace.roofline_pct(
        run, run.worker.get("shapes", {}).get("ssm_cost_per_step"),
        scope_trace.ms_per_step(run, "mamba", "scan"))

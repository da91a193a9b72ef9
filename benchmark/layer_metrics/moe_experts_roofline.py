"""kernel: the least time the chip could take for the routed experts'
grouped matmuls (benchlib/flops_moe.py::grouped_matmul_train_cost against
the peaks table; compute bounds it at these shapes: 25.1 ms of operations
against 11.8 ms of bytes) over the device time under the ``experts`` scope.
Moves tokens_per_s_per_chip."""


def read(run):
    from benchlib import moe_trace
    return moe_trace.roofline_pct(
        run, run.worker["shapes"].get("moe_cost_per_step"),
        moe_trace.mlp_ms_per_step(run, ("experts",)))

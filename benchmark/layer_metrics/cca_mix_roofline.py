"""kernel: the least time the chip could take for the passes between CCA's
projections and its kernel (benchlib/flops_zaya.py::cca_mix_train_cost
against the peaks table: five passes of 1,280 values a token a layer over the
HBM, and the convolution within heads on the MXU; the HBM bounds it at these
shapes) over the device time under the ``conv``, ``mix`` and ``rope`` scopes.
The passes are XLA's fusions today (the note ``cca_path`` on the trace span);
a kernel for them gives its name there. Moves tokens_per_s_per_chip."""


def read(run):
    from benchlib import manifest, moe_trace
    ms = manifest.load_reader("model.cca_mix_ms_per_step")(run)
    return moe_trace.roofline_pct(
        run, run.worker.get("shapes", {}).get("cca_mix_cost_per_step"), ms)

"""kernel: the least time the chip could take for the windowed layers'
attention cores (benchlib/flops_smallthinker.py::window_cores_train_cost
against the peaks table: six matmuls over the score entries a row's window
lets through, the band exactly and not the blocks a kernel walks; q, k, v, o,
dO and the three gradients once each) over the device time under
``attn/window`` (model.attn_window_ms_per_step). The band is the least work
there is, so no reading can pass 100%; what a kernel walks beyond it (the dead
part of the two blocks that straddle the band's edges: 70 block pairs a head
for 56 blocks' worth at 16,384 rows in blocks of 1,024) lowers it. Moves
tokens_per_s_per_chip."""


def read(run):
    from benchlib import manifest, moe_trace
    ms = manifest.load_reader("model.attn_window_ms_per_step")(run)
    return moe_trace.roofline_pct(
        run, run.worker.get("shapes", {}).get("window_cost_per_step"), ms)

"""The Laguna-XS.2 cell's step for the real chip, with no chip here (as
``test_tpu_compile_kimi_linear.py``): traced and lowered for a described
v5e in tier-1, which is where a step says which kernels it takes; handed
to the TPU compiler on demand (``-m slow``), which is where it says what
memory it asks for. Nothing runs, so nothing here is a result or a
time."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402
from conftest import (  # noqa: E402
    kernel_calls, lower_real_size_step, program_bytes, router_choice_calls)


@pytest.fixture(scope="module")
def real_size_step(v5e):
    """The cell's step as the builder makes it (layers 0-4 as published,
    ``F S S S F`` at 48, 64, 64, 64, 48 query heads; 32 of 256 experts;
    12,544 rows of the two tables; the blocks recomputed; adamw with a
    bf16 first moment) at 1 x 16,384 tokens, lowered once: (config, the
    trace's notes, the lowered program)."""
    from ray_tpu.models.laguna import Laguna, LagunaConfig, laguna_loss_fn
    cfg = LagunaConfig.laguna_xs_2(
        n_layer=5, vocab_size=12544, experts_held=(0, 32), seq_len=16384,
        remat=True)
    model = Laguna(cfg)
    return cfg, *lower_real_size_step(
        v5e[0], model, laguna_loss_fn(model, ce_chunk=2048),
        (1, cfg.seq_len))


def test_the_real_size_step_takes_the_kernels_it_should(real_size_step):
    """Every layer's core is one call of the multi-block flash kernels in
    the projections' own layout, the forward once and the backward once:
    the block is recomputed, and keeps its core's output and row
    statistics (``blocks_remat_keeps``, behind the routers' product,
    choice and counts, ``W_o``'s product, q, k and v, and the dense and
    shared MLPs' ``gate`` and ``up``), so the second pass over the
    block runs no forward kernel. The two full layers' under
    ``attn/core`` over 6,144 lanes, the three sliding layers' under
    ``attn/window`` over 8,192 lanes and the band of a 512-key window in
    blocks of the window's own 512 rows (``_window_block``: 63 block
    pairs of 512 x 512 a head, twice the band's area, where blocks of
    1,024 walked 31 of four times that and the causal grid walks 136 of
    1,024). No ``[T, T]`` array exists."""
    _, notes, lowered = real_size_step
    assert notes["attn_kind"] == "window_global"
    assert notes["attn_layers"] == "FSSSF"
    assert notes["attn_heads"] == "48,64,64,64,48"
    assert notes["attn_window"] == 512 and notes["blocks_remat"] is True
    assert notes["blocks_remat_keeps"] == (
        "moe_router_logits,moe_router_experts,moe_router_weights,"
        "moe_router_counts,moe_router_lse,attn_out_proj,attn_q,attn_k,attn_v,"
        "mlp_gate,mlp_up,attn_out,attn_lse")
    assert notes["attn_gate"] == "headwise_sigmoid"
    assert notes["rope_kind"] == "yarn_half|default"
    assert notes["rope_attention_factor"] == pytest.approx(1.4158883)
    # 48 and 64 heads of 128: one a 128-lane block of the projections'
    # layout, in both kinds of layer
    assert notes["flash_layout"] == "bthd"
    assert notes["flash_lanes_per_block"] == 128
    assert notes["flash_path"] == "multi_block"
    assert notes["flash_window"] == 512 and notes["flash_band_blocks"] == 63
    assert notes["flash_block_rows"] == 512
    assert notes["flash_band_area"] == pytest.approx(2.0, abs=1e-3)
    assert notes["flash_bwd_resident_rows"] == 16384
    assert notes["moe_router"] == "sigmoid"
    assert notes["moe_experts_held"] == [0, 32]
    assert notes["moe_rows_sorted"] == 32768 and notes["moe_routes"] == 131072
    assert notes["moe_path"] == "megablox_gmm"
    assert notes["moe_rows_path"] == "tgmm"
    # the four routers' choice: the kernel pair once a layer, nothing of
    # it under ``rematted_computation``, no ``top_k`` or gather left
    assert notes["moe_router_path"] == "pallas"
    router_choice_calls(lowered, 4, "f32[256,16384]", "i32[8,16384]")
    calls = kernel_calls(lowered)
    flash = [line for line in calls if "/attn/" in line]
    head = [line for line in calls if "jit(_ce_lse_fwd)" in line]
    assert len(head) == 1 and "/loss/" in head[0]
    # five layers: the forward kernel once (a recomputed block keeps
    # its results), the backward once
    assert len(flash) == 5 * 2
    assert all(re.search(r"/h_[04]/attn/core/|/h_[123]/attn/window/", line)
               for line in flash)
    core = [line for line in flash if "/attn/core/" in line]
    window = [line for line in flash if "/attn/window/" in line]
    assert len(core) == 2 * 2 and len(window) == 3 * 2
    assert sum("jit(_flash_fwd)" in line for line in core) == 2
    assert sum("jit(_flash_fwd)" in line for line in window) == 3
    assert sum("jit(_flash_bwd)" in line for line in flash) == 5
    assert all("bf16[1,16384,6144]" in line for line in core)
    assert all("bf16[1,16384,8192]" in line for line in window)
    # the rest are the routed layers' choice, grouped matmuls and row sums
    rest = [line for line in calls if line not in flash + head]
    assert rest and all(re.search(r"/h_[1234]/mlp/", line) for line in rest)
    assert "16384x16384" not in lowered.as_text()


@pytest.mark.slow
def test_the_real_size_step_compiles_inside_the_chips_memory(real_size_step):
    """Arguments + temporaries + unaliased outputs are 13.75 GB of the
    chip's 16.909 (15.75 GiB): 12.47 at PR 53, which kept the cores'
    results alone, and 1.27 more for what PR 66's list keeps of five
    blocks (2.6 GB of arrays; the step's peak was a block's backward
    pass, which held that block's own before). The pin is that figure
    with 0.6 GB either way and no longer "under 14.0", which was
    written in GiB's number against the 15.75: a step that keeps less
    than its list says is a fault here too, and the chip's allocator
    holds ~0.5 GB less than this sum (``device.hbm_held_gb``). Without
    ``remat`` the compiler asks for 16.64 GB and refuses. No fusion is
    XLA's own rematerialisation (``.remat`` in its name: what a step
    pays with when its list asks for more than fits, PR 62), and of a
    block's matmuls the second pass makes only the head gate's again
    (2048 -> 48 | 64, 0.4 ms a step: not worth a name)."""
    cfg, _, lowered = real_size_step
    compiled = lowered.compile()
    m, total = program_bytes(compiled)
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)    # f32 + bf16 + f32 a parameter
    print(f"laguna step: arguments + temporaries {total / 1e9:.2f} GB, "
          f"peak {m.peak_memory_in_bytes / 1e9:.2f}")
    assert 13.2e9 < total < 14.4e9      # 13.75 GB; 12.47 at PR 53
    text = compiled.as_text()
    assert not re.findall(r"^\s+%?[\w.\-]*\.remat\d* = ", text, re.M)
    again = re.findall(
        r"= \S+ convolution\(.*rematted_computation/(h_\d/[\w/]+)/dot_general",
        text)
    assert len(again) == 5 and all(
        re.fullmatch(r"h_\d/attn/gate/g", path) for path in again), again

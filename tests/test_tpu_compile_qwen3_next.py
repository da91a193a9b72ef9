"""The Qwen3-Next cell's step for the real chip, with no chip here (as
``test_tpu_compile_kimi_linear.py``): traced and lowered for a described
v5e in tier-1, compiled by the TPU compiler on demand (``-m slow``).
Nothing runs, so nothing here is a result or a time."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402
from conftest import (  # noqa: E402
    kernel_calls, kernel_kinds, lower_real_size_step, program_bytes,
    router_choice_calls)

GRAD_GROUPS = {
    "grad_norm_gdn_gates": "^h_[0-9]+/gdn/(A_log|dt_bias|ba/kernel)$",
    "grad_norm_attn_qk":
    "^h_[0-9]+/attn/(q/kernel|k/kernel|q_norm|k_norm)$"}


@pytest.fixture(scope="module")
def real_size_step(v5e):
    """The cell's step as the builder makes it (layers 0-3, LLLF, with 32
    of 512 experts held, 19,072 rows of each table, the blocks
    recomputed; adamw with a bf16 first moment) at 1 x 16,384 tokens,
    lowered once: (config, the trace's notes, the lowered program)."""
    from ray_tpu.models.qwen3_next import (
        Qwen3Next,
        Qwen3NextConfig,
        qwen3_next_loss_fn,
    )
    cfg = Qwen3NextConfig.qwen3_next_80b_a3b(
        n_layer=4, experts_held=(0, 32), vocab_size=19072, remat=True)
    model = Qwen3Next(cfg)
    return cfg, *lower_real_size_step(
        v5e[0], model, qwen3_next_loss_fn(model, ce_chunk=2048),
        (1, cfg.seq_len), grad_groups=GRAD_GROUPS)


def test_the_real_size_step_takes_the_kernels_it_should(real_size_step):
    """The three Gated DeltaNet layers run the recurrence's kernel pair
    with the scalar scores (each once a layer under ``gdn/scan``: a
    recomputed block keeps ``o`` and the chunk-entering states by name),
    fed q and k 16 heads wide as the one 8,192-channel convolution left
    them and ``g`` a float a row a head; the convolution is
    ``ops/pallas/causal_conv.py``'s pair under ``gdn/conv``, the output
    gate the ``silu`` pair of ``ops/pallas/gated_norm.py`` under
    ``gdn/out_gate``; the one attention layer is the flash pair at 256
    lanes a block under ``attn/core``; the 512-wide router's experts
    held run on the grouped matmuls, chosen by the routers' kernel pair;
    no ``[T, T]`` array exists."""
    cfg, notes, lowered = real_size_step
    assert notes["attn_kind"] == "gdn_gated"
    assert notes["attn_layers"] == "LLLF" and notes["blocks_remat"] is True
    assert notes["blocks_remat_keeps"] == (
        "moe_router_logits,moe_router_experts,moe_router_weights,"
        "moe_router_counts,moe_router_lse,mixer_out_proj,gdn_gated_out,"
        "kda_scan_out,kda_scan_states,gdn_in_proj,attn_out,attn_lse")
    assert notes["gdn_path"] == "pallas_chunked" and notes["gdn_chunk"] == 64
    assert notes["gdn_heads"] == [16, 32] and notes["gdn_state"] == [128, 128]
    assert notes["gdn_gate_path"] == "pallas"
    assert notes["conv_path"] == "pallas"
    assert (notes["conv_taps"], notes["conv_cols"]) == (4, 8192)
    assert notes["flash_path"] == "multi_block"
    assert notes["flash_layout"] == "bthd"
    assert notes["flash_lanes_per_block"] == 256
    assert notes["flash_bwd_resident_rows"] == 16384
    assert notes["moe_router"] == "softmax" and notes["moe_top_k"] == 10
    assert notes["moe_experts"] == 512
    assert notes["moe_experts_held"] == [0, 32]
    assert notes["moe_rows_sorted"] == 20480    # twice the even share
    assert notes["moe_routes"] == 163840
    assert notes["moe_path"] == "megablox_gmm"
    # the routers' choice: the kernel pair once a layer on the product
    # transposed, 512 experts down the sublanes; the recomputed blocks
    # keep its choice, weights, counts and lse, so neither it nor a
    # ``top_k`` runs under ``rematted_computation``
    assert notes["moe_router_path"] == "pallas"
    router_choice_calls(lowered, 4, "f32[512,16384]", "i32[10,16384]")
    assert "kda_path" not in notes and "kda_gate_path" not in notes
    calls = kernel_calls(lowered)
    kinds = kernel_kinds(calls)
    assert {"gmm", "tgmm"} <= set(kinds)
    assert kinds.count("_ce_lse_fwd") == 1
    assert kinds.count("_flash_fwd") == 1 and kinds.count("_flash_bwd") == 1
    flash = [line for kind, line in zip(kinds, calls) if "_flash_" in kind]
    assert all("/h_3/attn/core/" in line for line in flash)
    assert all("bf16[1,16384,4096]" in line for line in flash)
    assert kinds.count("_gdn_fwd") == 3 and kinds.count("_gdn_bwd") == 3
    assert "_kda_fwd" not in kinds and "_kda_bwd" not in kinds
    # the convolution: the forward in the step's forward pass and in
    # ``_gdn_core``'s recomputation, the backward once
    assert kinds.count("_conv_fwd") == 2 * 3
    assert kinds.count("_conv_bwd") == 3
    assert kinds.count("_head_silu_gate_fwd") == 3
    assert kinds.count("_head_silu_gate_bwd") == 3
    assert "_head_gate_fwd" not in kinds
    scope_of = {"_gdn_fwd": "scan", "_gdn_bwd": "scan",
                "_conv_fwd": "conv", "_conv_bwd": "conv",
                "_head_silu_gate_fwd": "out_gate",
                "_head_silu_gate_bwd": "out_gate"}
    under_gdn = [(kind, line) for kind, line in zip(kinds, calls)
                 if "/gdn/" in line]
    assert len(under_gdn) == 6 + 9 + 6
    assert all(re.search(
        r"/gdn/(checkpoint/|rematted_computation/)*%s/jit" % scope_of[kind],
        line) for kind, line in under_gdn)
    text = lowered.as_text(debug_info=True)
    assert "qk_norm/" not in text.replace("/attn/qk_norm/", "")
    for kind, line in under_gdn:
        if scope_of[kind] != "scan":
            continue
        # q and k (and their cotangents) 16 heads wide, bfloat16 as the
        # convolution left them; nothing of the decay 128 lanes a head:
        # ``g``, ``beta`` and their cotangents [1, 32, 128, 1, 128]
        keys = re.findall(r"(\w+)\[1,16384,2048\]", line)
        steps = re.findall(r"(\w+)\[1,32,128,1,128\]", line)
        wide = re.findall(r"(\w+)\[1,16384,4096\]", line)
        if kind == "_gdn_fwd":
            assert (keys, steps, wide) == (
                ["bf16"] * 2, ["f32"] * 2, ["bf16", "f32"]), line
            assert "rematted_computation" not in line, line
        else:
            assert (keys, steps) == (["bf16"] * 4, ["f32"] * 4), line
            assert wide == ["bf16", "f32", "bf16"], line
        assert line.count("f32[1,256,32,128,128]") == 1, line
    assert "16384x16384" not in text


@pytest.mark.slow
def test_the_real_size_step_compiles_inside_the_chips_memory(real_size_step):
    """Arguments + temporaries + unaliased outputs stay under the 14.5 GB
    that leave room for the device's own reserve, and no router's
    matmul is left under ``rematted_computation``."""
    cfg, _, lowered = real_size_step
    compiled = lowered.compile()
    m, total = program_bytes(compiled)
    print(f"peak {m.peak_memory_in_bytes / 1e9:.2f} GB")
    assert cfg.num_params() == 625_994_816
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)    # f32 + bf16 + f32 a parameter
    assert 4e9 < total <= 14.5e9
    assert not re.findall(
        r"= \S+ convolution\(.*rematted_computation/h_\d/mlp/router/",
        compiled.as_text())

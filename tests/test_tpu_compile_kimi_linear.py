"""The Kimi-Linear cell's step for the real chip, with no chip here (as
``test_tpu_compile_smallthinker.py``): traced and lowered for a described
v5e in tier-1, compiled by the TPU compiler on demand (``-m slow``).
Nothing runs, so nothing here is a result or a time."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402
from conftest import (  # noqa: E402
    kernel_calls, kernel_kinds, lower_real_size_step, program_bytes,
    router_choice_calls)


@pytest.fixture(scope="module")
def real_size_step(v5e):
    """The cell's step as the builder makes it (layers 1-5, KKKMK, with 8
    of 256 experts held, 20,480 rows of each table, the blocks
    recomputed; adamw with a bf16 first moment) at 1 x 16,384 tokens,
    lowered once: (config, the trace's notes, the lowered program)."""
    from ray_tpu.models.kimi_linear import (
        KimiLinear,
        KimiLinearConfig,
        kimi_linear_loss_fn,
    )
    cfg = KimiLinearConfig.kimi_linear_48b_a3b(
        n_layer=5, experts_held=(0, 8), vocab_size=20480, remat=True)
    model = KimiLinear(cfg)
    return cfg, *lower_real_size_step(
        v5e[0], model, kimi_linear_loss_fn(model, ce_chunk=2048),
        (1, cfg.seq_len), grad_groups={
            "grad_norm_kda_gates":
            "^h_[0-9]+/kda/(f_a/kernel|f_b|A_log|dt_bias|b/kernel)$"})


def test_the_real_size_step_takes_the_kernels_it_should(real_size_step):
    """The MLA layer's attention is the latent kernel pair with dq's
    16,384 rows resident and no rotation, the KDA layers run the
    recurrence's kernel pair (each once a layer, all under ``scan``
    under ``scan``: the forward in the step's forward pass alone, with
    the states entering every chunk among its results, because a
    recomputed block keeps ``o`` and those states by name and its
    policy reaches through ``_kda_core``'s checkpoint; PR 59, twice a
    layer before), which take ``q`` and ``k`` as the convolutions left
    them (no operation under ``kda/qk_norm``: the scope is the XLA
    path's), the three convolutions a layer are the kernel pair of
    ``ops/pallas/causal_conv.py`` (under ``conv``: the forward in both
    forward passes, the backward once; PR 55), the output gate is the
    second kernel pair of ``ops/pallas/gated_norm.py`` (under
    ``out_gate``, PR 58: the forward once a layer, in the step's forward
    pass, because a recomputed block keeps the gated output and
    ``_kda_core``'s recomputation needs ``o`` and ``gate`` of it, not
    its result; the backward once) and no other custom call stands
    under ``kda``, and no ``[T, T]`` array exists."""
    _, notes, lowered = real_size_step
    assert notes["attn_kind"] == "kda_mla"
    assert notes["attn_layers"] == "KKKMK" and notes["blocks_remat"] is True
    assert notes["blocks_remat_keeps"] == (
        "kda_gated_out,kda_scan_out,kda_scan_states,moe_router_logits,"
        "moe_router_experts,moe_router_weights,moe_router_counts,"
        "moe_router_lse,mixer_in_proj,mixer_stream,mlp_gate,mlp_up,"
        "attn_out,attn_lse")
    assert notes["kda_path"] == "pallas_chunked" and notes["kda_chunk"] == 64
    assert notes["kda_heads"] == 32 and notes["kda_state"] == [128, 128]
    assert notes["conv_path"] == "pallas"
    assert (notes["conv_taps"], notes["conv_cols"]) == (4, 4096)
    assert notes["kda_gate_path"] == "pallas"
    assert notes["flash_path"] == "mla_multi_block"
    assert notes["flash_bwd_resident_rows"] == 16384
    assert notes["mla_positions"] == "none"
    assert notes["mla_saved"] == "latents" and notes["dense_layers"] == 1
    assert notes["moe_router"] == "sigmoid" and notes["moe_top_k"] == 8
    assert notes["moe_experts_held"] == [0, 8]
    assert notes["moe_rows_sorted"] == 8192     # twice the even share
    assert notes["moe_path"] == "megablox_gmm"
    # the four routers' choice: the kernel pair once a layer, nothing of
    # it under ``rematted_computation``, no ``top_k`` or gather left
    assert notes["moe_router_path"] == "pallas"
    router_choice_calls(lowered, 4, "f32[256,16384]", "i32[8,16384]")
    calls = kernel_calls(lowered)
    kinds = kernel_kinds(calls)
    assert {"gmm", "tgmm"} <= set(kinds)
    assert kinds.count("_ce_lse_fwd") == 1      # the head's forward (PR 51)
    # the forward kernel once (the block is recomputed and keeps its
    # core's output and row statistics), the backward once
    assert kinds.count("mla_flash_fwd") == 1
    assert kinds.count("mla_flash_bwd") == 1
    flash = [line for kind, line in zip(kinds, calls) if "mla_flash" in kind]
    assert all("/h_3/attn/core/" in line for line in flash)
    # four KDA layers: each kernel lowered once, called a layer, the
    # forward in the first pass alone (its results are kept by name)
    assert kinds.count("_kda_fwd") == 4
    assert kinds.count("_kda_bwd") == 4
    # and their twelve convolutions: the forward in the step's forward
    # pass and in ``_kda_core``'s recomputation, the backward once
    assert kinds.count("_conv_fwd") == 2 * 12
    assert kinds.count("_conv_bwd") == 12
    # and their output gates, each pass once
    assert kinds.count("_head_gate_fwd") == 4
    assert kinds.count("_head_gate_bwd") == 4
    assert "_norm_fwd" not in kinds and "_norm_bwd" not in kinds
    under_kda = [(kind, line) for kind, line in zip(kinds, calls)
                 if "/kda/" in line]
    assert len(under_kda) == 8 + 36 + 8
    # the checkpoints' own names stand between the module and its scope
    scope_of = {"_kda_fwd": "scan", "_kda_bwd": "scan",
                "_conv_fwd": "conv", "_conv_bwd": "conv",
                "_head_gate_fwd": "out_gate", "_head_gate_bwd": "out_gate"}
    for kind, line in under_kda:
        if scope_of[kind] == "out_gate":
            # the recurrence's float32 ``o`` and its cotangent, and
            # nothing else of that width, at a kernel's edge
            rows = re.findall(r"(\w+)\[1,16384,4096\]", line)
            assert rows == (["f32", "bf16", "bf16"]
                            if kind == "_head_gate_fwd" else
                            ["f32", "bf16", "bf16", "f32", "bf16"]), line
    assert all(re.search(
        r"/kda/(checkpoint/|rematted_computation/)*%s/jit" % scope_of[kind],
        line) for kind, line in under_kda)
    under_kda = [(kind, line) for kind, line in under_kda
                 if scope_of[kind] == "scan"]
    # the kernels bring q and k to unit length in their cells, from the
    # convolutions' bfloat16 rows, and return those rows' cotangents
    text = lowered.as_text(debug_info=True)
    assert "qk_norm" not in text
    for kind, line in under_kda:
        rows = re.findall(r"(\w+)\[1,16384,4096\]", line)
        assert rows.count("bf16") == (3 if kind == "_kda_fwd" else 6), line
        # the state entering each of the 256 chunks, float32: the
        # forward's second result (``keep_states``), the backward's
        # sixth operand, and no forward call is without it
        assert line.count("f32[1,256,32,128,128]") == 1, line
        if kind == "_kda_fwd":
            assert "rematted_computation" not in line, line
    assert "/attn/rope/" not in text and "/attn/q_down/" not in text
    assert "16384x16384" not in text


@pytest.mark.slow
def test_the_real_size_step_compiles_inside_the_chips_memory(real_size_step):
    """Arguments + temporaries + unaliased outputs stay inside the chip's
    16.909 GB (15.75 GiB) with the room the issue left it, 16.1 (12.13 GB
    at PR 47,
    12.92 before the norm of q and k moved into the kernels; 12.05 with
    the convolutions' XLA fusions, 11.96 with their kernels, PR 55;
    11.53 with the output gate's, PR 58). PR 59's 13.06: the 1.53 GB
    more are the recurrence's ``o`` and chunk-entering states (0.8 GB a
    KDA layer) held from a block's first pass to its backward, and they
    bought the forward kernel's second run a layer, 55 ms of a 684 ms
    step. PR 66's 13.09: the four routers' float32 product and choice
    (17 MB a layer) kept too, and no router's matmul left under
    ``rematted_computation``. **PR 70's 15.44** (``peak_memory_in_bytes``
    12.61 -> 14.92): the blocks' plain matmul products kept by name, a KDA
    mixer's ``W_q h``, ``W_k h``, ``W_v h`` (3 x 134 MB a layer, 1.61 GB
    over four), the stream behind either mixer (75 MB a layer, 0.38 GB:
    its output projection has no reader left), the dense MLP's ``gate`` and ``up`` (0.60 GB) and the four shared
    experts' (0.27 GB): 2.86 GB of arrays for 2.34 GB of program (a block's
    backward held its own share at the parent's peak), and no matmul wider
    than ``_kda_core``'s own rank-128 pairs left in the second pass. No
    fusion is XLA's own rematerialisation (``.remat`` in its name: what a
    list too long for the chip gets instead of a refusal)."""
    cfg, _, lowered = real_size_step
    compiled = lowered.compile()
    m, total = program_bytes(compiled)
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)    # f32 + bf16 + f32 a parameter
    assert 15.0e9 < total <= 15.44e9 + 0.1e9    # 13.09 at PR 66
    assert m.peak_memory_in_bytes <= 14.92e9 + 0.1e9 < 16.1e9
    text = compiled.as_text()
    assert not re.findall(r"^\s+%?[\w.\-]*\.remat\d* = ", text, re.M)
    assert not re.findall(
        r"= \S+ convolution\(.*rematted_computation/h_\d/mlp/router/", text)
    # the second pass's matmuls: the narrow projections of a KDA mixer's
    # input (the decay's and the gate's pairs, the step sizes) and the
    # latent layer's down projection, and no product of the lists'
    again = set(re.findall(
        r"rematted_computation/h_\d/(\w+/\w+/\w+)/dot_general", text))
    assert again <= {"kda/decay/f_a", "kda/decay/b", "kda/out_gate/g_a",
                     "attn/kv_down/proj"}, again

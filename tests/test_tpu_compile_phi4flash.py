"""The Phi-4-mini-flash cell's step compiles for the real chip, with no
chip here (as ``test_tpu_compile_kimi_linear.py``: the TPU compiler for a
described v5e; nothing runs, so nothing here is a result or a time)."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402
from conftest import (  # noqa: E402
    kernel_calls, lower_real_size_step, program_bytes)


@pytest.fixture(scope="module")
def real_size_step(v5e):
    """The cell's step as the builder makes it (the rule at eight
    layers, ``MSMSMFGX``, 25,088 rows of the tied table, the blocks
    recomputed; adamw with a bf16 first moment) at 1 x 4,096 tokens,
    lowered once: (config, the trace's notes, the lowered program)."""
    from ray_tpu.models.phi4flash import (
        Phi4Flash,
        Phi4FlashConfig,
        phi4flash_loss_fn,
    )
    cfg = Phi4FlashConfig.phi_4_mini_flash_reasoning(
        n_layer=8, vocab_size=25088, remat=True)
    model = Phi4Flash(cfg)
    return cfg, *lower_real_size_step(
        v5e[0], model, phi4flash_loss_fn(model, ce_chunk=2048),
        (1, cfg.seq_len), grad_groups={
            "grad_norm_mamba_ssm": "^h_[0-9]+/mamba/",
            "grad_norm_attn_diff":
            "^h_[0-9]+/attn/(lambda_[qk][12]|subln|out/kernel)$",
            "grad_norm_yoco_kv": "^h_5/attn/qkv/kernel$"})


def test_the_real_size_step_takes_the_kernels_it_should(real_size_step):
    """Every attention layer's four products are one call of the
    multi-block flash kernels over 80 heads of 64 in the projections' own
    layout, the windowed layers' under the band of a 512-key window in
    blocks of the window's 512 rows (``_window_block``: 15 block pairs
    of 512 x 512 a head where blocks of 1,024 walked 7 of four times the
    area and the causal grid walks 10 of 1,024); the three scans run the kernel pair of
    ``ops/pallas/mamba1_scan.py`` (custom calls under ``mamba/../scan``,
    no ``while`` loop there), their convolutions that of
    ``ops/pallas/causal_conv.py`` (under ``mamba/../conv``; PR 55) and no
    ``[T, T]`` array exists."""
    _, notes, lowered = real_size_step
    assert notes["attn_kind"] == "differential"
    assert notes["layer_pattern"] == "MSMSMFGX"
    assert notes["blocks_remat"] is True and notes["attn_window"] == 512
    assert notes["blocks_remat_keeps"] == (
        "mlp_gate_up,mixer_in_proj,mixer_stream,attn_q,attn_k,attn_v,"
        "mamba1_scan_out,mamba1_scan_states,attn_out,attn_lse")
    assert notes["ssm_kind"] == "mamba1" and notes["ssm_tokens"] == 4096
    assert (notes["ssm_inner"], notes["ssm_state"], notes["ssm_dt_rank"],
            notes["ssm_chunk"]) == (5120, 16, 160, 64)   # the kernels' rows
    assert notes["ssm_path"] == "pallas_chunked"
    assert notes["conv_path"] == "pallas"
    assert (notes["conv_taps"], notes["conv_cols"]) == (4, 5120)
    assert notes["attn_pairs"] == [20, 10] and notes["attn_products"] == 4
    assert notes["attn_calls"] == 1
    assert (notes["yoco_memory_layer"], notes["yoco_kv_layer"]) == (4, 5)
    # 80 heads of 64, two a 128-lane block of the projections' layout
    assert notes["flash_layout"] == "bthd"
    assert notes["flash_lanes_per_block"] == 128
    assert notes["flash_path"] == "multi_block"
    assert notes["flash_window"] == 512 and notes["flash_band_blocks"] == 15
    assert notes["flash_block_rows"] == 512
    assert notes["flash_band_area"] == pytest.approx(2.0, abs=1e-3)
    assert notes["flash_bwd_resident_rows"] == 4096
    calls = kernel_calls(lowered)
    convs = [line for line in calls if "/mamba/" in line
             and "/conv/" in line]
    scans = [line for line in calls if "/mamba/" in line
             and line not in convs]
    head = [line for line in calls if "jit(_ce_lse_fwd)" in line]
    calls = [line for line in calls if "/mamba/" not in line
             and line not in head]
    # the head's forward (PR 51): one kernel under ``loss``
    assert len(head) == 1 and "/loss/" in head[0]
    # four attention layers: the forward kernel once (the block is
    # recomputed and keeps its core's output and row statistics, and its
    # MLP's gate_up product), the backward once, each over
    # [1, 4096, 80 * 64]
    assert len(calls) == 4 * 2
    assert sum("jit(_flash_fwd)" in line for line in calls) == 4
    assert sum("jit(_flash_bwd)" in line for line in calls) == 4
    assert all(re.search(r"/h_[1357]/attn/(window|core|cross)/", line)
               for line in calls)
    assert sum("/attn/window/" in line for line in calls) == 2 * 2
    assert sum("/attn/cross/" in line for line in calls) == 2
    assert all("bf16[1,4096,5120]" in line for line in calls)
    assert not any("/gmu/" in line for line in calls)
    # three Mamba layers, each over [1, 4096, 5120]: the scan's forward
    # kernel in the block's forward pass alone (PR 70: the block keeps its
    # two results by the forward rule's names; twice a layer before), the
    # backward once
    assert all(re.search(r"/h_[024]/mamba/.*scan/", line) for line in scans)
    forward = [line for line in scans if "scan/jit(_mamba1_fwd)/" in line]
    assert len(forward) == 3
    assert not [line for line in forward if "rematted_computation" in line]
    assert sum("scan/jit(_mamba1_bwd)/" in line for line in scans) == 3
    assert len(scans) == 3 * 2
    # and their convolutions: the forward in the block's forward pass
    # and in its recomputation, the backward once
    assert all(re.search(r"/h_[024]/mamba/.*conv/", line) for line in convs)
    assert sum("conv1d/jit(_conv_fwd)/" in line for line in convs) == 3 * 2
    assert sum("conv1d/jit(_conv_bwd)/" in line for line in convs) == 3
    assert len(convs) == 3 * 3
    # no loop under the scans' scope: an operation in a loop's body is
    # named under ``while``
    text = lowered.as_text(debug_info=True)
    assert not [name for name in re.findall(r'loc\("([^"]*)"', text)
                if "/mamba/" in name and "/scan/" in name
                and "while" in name]
    assert "4096x4096" not in text


@pytest.mark.slow
def test_the_real_size_step_compiles_inside_the_chips_memory(real_size_step):
    """Arguments + temporaries + unaliased outputs stay inside the chip's
    16.909 GB (15.75 GiB) with the 13.0 the issue gave what the cell may
    hold (12.87 GB at PR 48, 12.31 GB since the scans' kernels: PR
    49; the convolutions' kernels, PR 55, leave it unmoved; **12.06 GB
    with each block's ``gate_up`` product kept, 168 MB a layer, 1.34 GB
    in all: PR 62**, no more than without them (the step's peak,
    ``peak_memory_in_bytes``, reads 11.72 GB where it read 11.77: it
    stands where the kept products do not all lie; where, was not
    read). **PR 70's 13.05** (``peak_memory_in_bytes`` 11.72 -> 12.42; the
    chip's allocator held 12.59 of it): 0.8 GB of arrays kept by name, the Mamba mixers' ``in_proj`` products
    (84 MB a layer, the gated memory unit's 42), the stream behind every
    mixer (21 MB), an attention layer's q, k, v as projected (42 MB), the
    scans' ``y`` (float32, 84 MB) and entering states (21 MB). With the
    cores' operands kept as ``repeat`` writes them out (126 MB a layer
    where 42) it read 13.26, over what 13.0 held allows. No fusion is XLA's own
    rematerialisation (``.remat`` in its name), and the scans' forward
    kernel stands in the first pass alone."""
    cfg, _, lowered = real_size_step
    compiled = lowered.compile()
    m, total = program_bytes(compiled)
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)    # f32 + bf16 + f32 a parameter
    assert 12.5e9 < total <= 13.05e9 + 0.1e9    # 12.06 at PR 62
    assert m.peak_memory_in_bytes <= 12.42e9 + 0.1e9
    text = compiled.as_text()
    assert not re.findall(r"^\s+%?[\w.\-]*\.remat\d* = ", text, re.M)
    scans = re.findall(r'op_name="[^"]*jit\(_mamba1_fwd\)[^"]*"', text)
    assert scans and not [s for s in scans if "rematted_computation" in s]
    again = set(re.findall(
        r"rematted_computation/h_\d/(\w+/\w+)/dot_general", text))
    assert again <= {"mamba/x_proj", "mamba/dt"}, again

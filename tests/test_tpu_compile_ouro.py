"""The Ouro-2.6B cell's step for the real chip, with no chip here (as
``test_tpu_compile_laguna.py``): traced and lowered for a described v5e
in tier-1, which is where a step says which kernels it takes and how many
blocks the program holds; handed to the TPU compiler on demand (``-m
slow``), which is where it says what memory it asks for. Nothing runs,
so nothing here is a result or a time."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402
from conftest import (  # noqa: E402
    kernel_calls, lower_real_size_step, program_bytes)


@pytest.fixture(scope="module")
def real_size_step(v5e):
    """The cell's step as the builder makes it (8 of the 48 layers run
    four times on one parameter tree, every width and the whole
    vocabulary as published, the blocks recomputed; adamw with a bf16
    first moment) at 1 x 4,096 tokens, lowered once: (config, the trace's
    notes, the lowered program)."""
    from ray_tpu.models.ouro import Ouro, OuroConfig, ouro_loss_fn
    cfg = OuroConfig.ouro_2_6b(n_layer=8, seq_len=4096, remat=True)
    model = Ouro(cfg)
    return cfg, *lower_real_size_step(
        v5e[0], model, ouro_loss_fn(model, ce_chunk=2048),
        (1, cfg.seq_len),
        grad_groups={"grad_norm_blocks": "^h_[0-9]+/",
                     "grad_norm_head": "^lm_head/"})


def test_the_real_size_step_takes_the_kernels_it_should(real_size_step):
    """**The program holds 8 blocks, not 32**: the passes are one loop,
    so each block's core is one call site of the multi-block flash
    forward kernel and one of the backward (a recomputed block keeps its
    core's output and row statistics, so the second pass over the block
    runs no forward kernel; it keeps two or three of its MLP's products
    too, which are XLA's matmuls and no call site here), ``[1, 4096, 16
    x 128]`` in the projections' own layout, under ``attn/core``. The
    four passes' rows reach the loss's forward kernel in one call:
    16,384 rows against the 49,152-row head. No ``[T, T]`` array exists."""
    cfg, notes, lowered = real_size_step
    assert notes["attn_kind"] == "looped_full"
    assert notes["ut_steps"] == 4 and notes["ut_path"] == "scan"
    assert notes["rope_kind"] == "half" and notes["blocks_remat"] is True
    assert notes["blocks_remat_keeps"] == (
        "mlp_down,mlp_up,mlp_gate[4:],attn_out,attn_lse")
    assert notes["flash_layout"] == "bthd"
    assert notes["flash_lanes_per_block"] == 128
    assert notes["flash_path"] == "multi_block"
    assert notes["ce_path"] == "pallas_lse"
    assert notes["ce_fwd_tile"] == 1024
    calls = kernel_calls(lowered)
    flash = [line for line in calls if "/attn/" in line]
    head = [line for line in calls if "jit(_ce_lse_fwd)" in line]
    assert len(head) == 1 and "/loss/" in head[0]
    assert "bf16[16384,2048]" in head[0] and "49152" in head[0]
    assert len(flash) == cfg.n_layer * 2
    assert all(re.search(r"/h_[0-7]/attn/core/", line) for line in flash)
    assert sum("jit(_flash_fwd)" in line for line in flash) == cfg.n_layer
    assert sum("jit(_flash_bwd)" in line for line in flash) == cfg.n_layer
    assert all("bf16[1,4096,2048]" in line for line in flash)
    assert sorted(calls) == sorted(flash + head)
    assert "4096x4096" not in lowered.as_text()


@pytest.mark.slow
def test_the_real_size_step_compiles_inside_the_chips_memory(real_size_step):
    """The step's peak, ``memory_analysis().peak_memory_in_bytes``, stays
    under 15.9 GB of the chip's 16.91 (15.75 GiB): 15.78 GB with
    ``down``'s and ``up``'s products kept in every layer and ``gate``'s
    in four of the eight (11.16 without them, 15.06 with the two
    alone), which is what the chip's allocator reserves (9.70 GB beside
    6.12 of arguments: my chip run, PR 62). **Arguments + temporaries,
    the sum the other cells' tests hold and ``device.program_gb``
    reports, reads 20.43 GB here and is not what the program takes**:
    once a looped step's peak passes ~11.8 GB the compiler's
    ``temp_size_in_bytes`` counts ~4.5 GB more than its own buffer
    assignment holds (11.46 GB at the parent, where the two agree to
    0.3; PERF.md section 6, PR 62). Held too, so that a change of
    either shows. No fusion of the compiled step is XLA's own
    rematerialisation (``.remat`` in its name), which is what the step
    pays with when it is asked to keep more than fits."""
    cfg, _, lowered = real_size_step
    compiled = lowered.compile()
    m, total = program_bytes(compiled)
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)    # f32 + bf16 + f32 a parameter
    print(f"ouro step: peak {m.peak_memory_in_bytes / 1e9:.2f} GB, "
          f"arguments + temporaries {total / 1e9:.2f}, arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f}, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f}, code "
          f"{m.generated_code_size_in_bytes / 1e9:.3f}")
    assert 9.0e9 < m.peak_memory_in_bytes < 15.78e9 + 0.1e9
    assert total < 20.43e9 + 0.1e9
    assert not re.findall(r"^\s+%?[\w.\-]*\.remat\d* = ", compiled.as_text(),
                          re.M)

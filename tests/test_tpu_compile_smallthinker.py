"""The SmallThinker cell's step compiles for the real chip, with no chip
here (as ``test_tpu_compile_zaya.py``: the TPU compiler for a described
v5e; nothing runs, so nothing here is a result or a time)."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402
from conftest import (  # noqa: E402
    kernel_calls, kernel_kinds, lower_real_size_step, program_bytes,
    router_choice_calls)


@pytest.fixture(scope="module")
def real_size_step(v5e):
    """The cell's step as the builder makes it (one period of four
    layers with 16 of 64 experts held, 19,072 rows of each table; adamw
    with a bf16 first moment) at 1 x 16,384 tokens, lowered once:
    (config, the trace's notes, the lowered program)."""
    from ray_tpu.models.smallthinker import (
        SmallThinker,
        SmallThinkerConfig,
        smallthinker_loss_fn,
    )
    cfg = SmallThinkerConfig.smallthinker_21b_a3b(
        n_layer=4, experts_held=(0, 16), vocab_size=19072)
    model = SmallThinker(cfg)
    return cfg, *lower_real_size_step(
        v5e[0], model, smallthinker_loss_fn(model, ce_chunk=2048),
        (1, cfg.seq_len))


def test_the_real_size_step_takes_the_kernels_it_should(real_size_step):
    """Every layer's attention is the equal-width multi-block kernel, the
    windowed layers' under a window of 4,096 with the band's 70 block
    pairs a head and not the causal grid's 136 (in ``_pick_block``'s
    1,024 rows: a window of four blocks bypasses ``_window_block``'s
    smaller ones), its backward pass ONE
    kernel a layer with dq's 16,384 rows resident (four ``_flash_bwd``
    custom calls), each kernel's call under its layer's scope
    (``attn/core`` in layer 0, ``attn/window`` in layers 1-3), and no
    ``[T, T]`` array exists."""
    _, notes, lowered = real_size_step
    assert notes["flash_path"] == "multi_block"
    assert notes["flash_layout"] == "bthd"
    assert notes["flash_window"] == 4096
    assert notes["flash_band_blocks"] == 70 < 16 * 17 // 2
    assert notes["flash_block_rows"] == 1024
    assert notes["flash_band_area"] == pytest.approx(1.25)
    assert notes["flash_bwd_resident_rows"] == 16384
    assert notes["attn_kind"] == "window_global"
    assert notes["attn_layers"] == "gWWW"
    assert notes["moe_router_input"] == "pre_attention"
    assert notes["moe_router"] == "caller" and notes["moe_top_k"] == 6
    assert notes["moe_expert_kind"] == "reglu"
    assert notes["moe_experts_held"] == [0, 16]
    assert notes["moe_rows_sorted"] == 49152    # twice the even share
    assert notes["moe_path"] == "megablox_gmm"
    # the routers in front of attention choose by the kernel pair
    # (``route_softmax``), once a layer; the counts stay
    # ``routed_experts``' scatter-add
    assert notes["moe_router_path"] == "pallas"
    router_choice_calls(lowered, 4, "f32[64,16384]", "i32[6,16384]",
                        counts_scattered=True)
    calls = kernel_calls(lowered)
    kinds = kernel_kinds(calls)
    assert set(kinds) == {"_flash_fwd", "_flash_bwd", "gmm", "tgmm",
                          "_ce_lse_fwd", "_choice_fwd", "_choice_bwd"}
    assert kinds.count("_ce_lse_fwd") == 1      # the head's forward (PR 51)
    assert notes["ce_path"] == "pallas_lse"
    assert kinds.count("_flash_fwd") == 4
    assert kinds.count("_flash_bwd") == 4       # one kernel a layer
    flash = [line for kind, line in zip(kinds, calls) if "_flash_" in kind]
    assert sum("/h_0/attn/core/" in line for line in flash) == 2
    assert sum("/attn/window/" in line for line in flash) == 6
    assert not any("/h_0/attn/window/" in line for line in flash)
    # the routed layer's row moves (PR 43): each token's sum is a gather
    # and a tgmm under combine, its transpose the same under the call's
    # scope, dispatch (a layer's first slab; the loops over further slabs
    # sum on the plain path)
    assert notes["moe_rows_path"] == "tgmm"
    sums = [line for kind, line in zip(kinds, calls)
            if kind == "tgmm" and "jit(_sum)" in line]
    assert not any("/while/body/" in line for line in sums)
    assert sum("/mlp/combine/jit(_sum)/jit(tgmm)" in line for line in sums) == 4
    assert sum(bool(re.search(r"/mlp/\S*dispatch\S*/jit\(_sum\)/jit\(tgmm\)",
                              line)) for line in sums) == 4
    assert "16384x16384" not in lowered.as_text()


@pytest.mark.slow
def test_the_real_size_step_compiles_inside_the_chips_memory(real_size_step):
    """Arguments + temporaries + unaliased outputs stay under the 15.0 GB
    at which the configuration file's ``cut.memory`` would have turned to
    ``remat``; what only the compiled text says: no scatter under the
    routed layer's ``dispatch`` and ``combine``."""
    cfg, _, lowered = real_size_step
    compiled = lowered.compile()
    m, total = program_bytes(compiled)
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)    # f32 + bf16 + f32 a parameter
    assert 0.25 * 15.75e9 < total <= 15.0e9
    text = compiled.as_text()
    assert not [line for line in text.splitlines()
                if " scatter(" in line and re.search(
                    r"/mlp/[^ \"]*(dispatch|combine)", line)]
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    print("VMEM of the sums' tgmm:", sorted({
        re.search(r'used_scoped_memory_configs[^]]*?"size":"(\d+)"', line)
        .group(1) for kind, line in zip(kernel_kinds(calls), calls)
        if kind == "tgmm" and "jit(_sum)" in line and "experts" not in line}))

"""The ZAYA1-8B cell's step compiles for the real chip, with no chip here
(as ``test_tpu_compile_joyai.py``: the TPU compiler for a described v5e;
nothing runs, so nothing here is a result or a time)."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402
from conftest import (  # noqa: E402
    kernel_calls, kernel_kinds, lower_real_size_step, program_bytes,
    scope_primitives)


@pytest.fixture(scope="module")
def real_size_step(v5e):
    """The cell's step as the builder makes it (five layers with 8 of
    16 experts held, 32,896 rows of the tied table; adamw with a bf16
    first moment) at 2 x 8,192 tokens, lowered once: (config, the
    trace's notes, the lowered program)."""
    from ray_tpu.models.zaya import Zaya, ZayaConfig, zaya_loss_fn
    cfg = ZayaConfig.zaya1_8b(n_layer=5, experts_held=(0, 8),
                              vocab_size=32896)
    model = Zaya(cfg)
    return cfg, *lower_real_size_step(
        v5e[0], model, zaya_loss_fn(model, ce_chunk=2048),
        (2, cfg.seq_len))


def test_the_real_size_step_takes_the_kernels_it_should(real_size_step):
    """Every layer's attention is the equal-width multi-block kernel with
    ONE backward kernel a layer (dq's 8,192 rows resident), CCA's passes
    in front of it are ``ops/pallas/cca_mix.py``'s pair (two kinds of
    custom call beside the flash kernels' two and the grouped matmuls'
    two, one of each a layer, under ``attn/mix``), the loss's forward is
    ``ops/pallas/ce_lse.py``'s one call, and no ``[T, T]`` array
    exists."""
    _, notes, lowered = real_size_step
    assert notes["flash_path"] == "multi_block"
    assert notes["flash_layout"] == "bthd"
    assert notes["flash_bwd_resident_rows"] == 8192
    assert notes["cca_path"] == "pallas" and notes["attn_kind"] == "cca"
    assert notes["cca_halo_rows"] == 2
    assert 8192 % notes["cca_rows_per_block"] == 0
    assert notes["moe_router"] == "caller" and notes["moe_top_k"] == 1
    assert notes["moe_experts_held"] == [0, 8]
    assert notes["moe_rows_sorted"] == 16384    # twice the even share: all
    assert notes["moe_path"] == "megablox_gmm"
    # its router is its own MLP and arg-max: no choice of ``ops/moe.py``'s
    # is made, on either path (PR 68 changed nothing here)
    assert "moe_router_path" not in notes
    assert not scope_primitives(lowered, "/mlp/router/") & {
        "jit(_choice_fwd)", "jit(_choice_bwd)", "top_k"}
    calls = kernel_calls(lowered)
    kinds = kernel_kinds(calls)
    assert set(kinds) == {"_flash_fwd", "_flash_bwd", "gmm", "tgmm",
                          "_mix_fwd", "_mix_bwd", "_ce_lse_fwd"}
    # the head's forward (PR 51): one kernel over the 16,384 rows under
    # ``loss``, no scan
    assert notes["ce_path"] == "pallas_lse"
    assert [("/loss/" in line, "/while/" in line) for kind, line
            in zip(kinds, calls) if kind == "_ce_lse_fwd"] == [(True, False)]
    assert kinds.count("_mix_fwd") == kinds.count("_mix_bwd") == 5
    assert kinds.count("_flash_fwd") == 5
    assert kinds.count("_flash_bwd") == 5       # one kernel a layer
    assert all("/attn/mix/jit(_mix_" in line
               for kind, line in zip(kinds, calls) if "_mix_" in kind)
    # the routed layer's row moves (PR 43): each token's sum is a gather
    # and a tgmm under combine, its transpose the same under the call's
    # scope, dispatch (a layer's first slab; the loops over further slabs
    # sum on the plain path)
    assert notes["moe_rows_path"] == "tgmm"
    sums = [line for kind, line in zip(kinds, calls)
            if kind == "tgmm" and "jit(_sum)" in line]
    assert not any("/while/body/" in line for line in sums)
    assert sum("/mlp/combine/jit(_sum)/jit(tgmm)" in line for line in sums) == 5
    assert sum(bool(re.search(r"/mlp/\S*dispatch\S*/jit\(_sum\)/jit\(tgmm\)",
                              line)) for line in sums) == 5
    assert "8192x8192" not in lowered.as_text()


@pytest.mark.slow
def test_the_real_size_step_compiles_inside_the_chips_memory(real_size_step):
    """Arguments + temporaries + unaliased outputs stay under the 15.0 GB
    at which the configuration file's ``cut.memory`` would have gone to
    four layers and at no more than the 13.17 GB of the step before CCA's
    kernels; what only the compiled text says: no pass over float32
    logits behind the head's kernel, and no scatter under the routed
    layer's ``dispatch`` and ``combine``."""
    cfg, _, lowered = real_size_step
    compiled = lowered.compile()
    m, total = program_bytes(compiled)
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)    # f32 + bf16 + f32 a parameter
    assert 0.25 * 15.75e9 < total <= 13.17e9
    text = compiled.as_text()
    assert "exponential_reduce" not in text
    assert not [line for line in text.splitlines()
                if " scatter(" in line and re.search(
                    r"/mlp/[^ \"]*(dispatch|combine)", line)]
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    print("VMEM of the sums' tgmm:", sorted({
        re.search(r'used_scoped_memory_configs[^]]*?"size":"(\d+)"', line)
        .group(1) for kind, line in zip(kernel_kinds(calls), calls)
        if kind == "tgmm" and "jit(_sum)" in line and "experts" not in line}))

"""The granite-4.0-h-micro cell's step for the real chip, with no chip
here (as ``test_tpu_compile_ouro.py``): traced and lowered for a described
v5e in tier-1, which is where a step says which kernels it takes and how
often; handed to the TPU compiler on demand (``-m slow``), which is where
it says what memory it asks for. Nothing runs, so nothing here is a result
or a time."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from conftest import (  # noqa: E402
    kernel_calls, kernel_kinds, lower_real_size_step, on_device,
    program_bytes)

CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "granite-4.0-h-micro.json")


@pytest.fixture(scope="module")
def real_size_step(v5e):
    """The cell's step as the builder makes it (one period of ten
    layers, every width as published, a quarter of the tied table, the
    blocks recomputed; adamw with a bf16 first moment) at 1 x 8,192
    tokens, lowered once: (config, the trace's notes, the lowered
    program)."""
    import json
    from ray_tpu.models.granite import (
        Granite, GraniteHybridConfig, granite_loss_fn)
    with open(CONFIG_FILE) as f:
        groups = json.load(f)["reference"]["grad_groups"]
    full = GraniteHybridConfig.granite_4_0_h_micro()
    cfg = GraniteHybridConfig.granite_4_0_h_micro(
        layer_types=full.layer_types[:10], vocab_size=25088, remat=True)
    model = Granite(cfg)
    return cfg, *lower_real_size_step(
        v5e[0], model, granite_loss_fn(model, ce_chunk=2048),
        (1, cfg.seq_len), grad_groups=groups)


def test_the_real_size_step_takes_the_kernels_it_should(real_size_step):
    """Nine Mamba layers, each one call site of the scan's forward
    kernel and one of its backward (**nine forwards, not eighteen**: a
    recomputed block keeps ``y`` and the entering states by name), at
    one group of 64 heads (eight head blocks a group) and chunk 256;
    the convolution's and the gated norm's pairs run again in the
    recomputed block (their forward twice a layer: nothing of theirs is
    kept); the one attention layer's multi-block flash pair at ``[1,
    8192, 32 x 64]`` once each; the head's forward kernel once."""
    cfg, notes, lowered = real_size_step
    assert cfg.num_params() == 797_850_560
    assert notes["attn_kind"] == "gqa_nope_scaled"
    assert notes["attn_scale"] == 0.015625
    assert notes["layer_pattern"] == "MMMMM*MMMM"
    assert notes["blocks_remat"] is True
    assert notes["blocks_remat_keeps"] == (
        "mlp_gate_up,mamba_z,mamba_xbc,mamba_dt,mixer_stream,"
        "ssd_scan_out,ssd_scan_states,attn_out,attn_lse")
    assert notes["ssm_path"] == "pallas_chunked"
    assert notes["ssm_groups"] == 1 and notes["ssm_chunk"] == 256
    assert notes["ssm_blocks_per_group"] == 8
    assert notes["gate_norm_path"] == "pallas"
    assert notes["conv_path"] == "pallas"
    assert notes["flash_path"] == "multi_block"
    assert notes["ce_path"] == "pallas_lse"
    calls = kernel_calls(lowered)
    mamba = [line for line in calls if "/mamba/" in line]
    attn = [line for line in calls if "/attn/" in line]
    head = [line for line in calls if "/loss/" in line]
    assert sorted(calls) == sorted(mamba + attn + head)
    assert all(re.search(r"/h_[0-46-9]/mamba/", line) for line in mamba)
    kinds = kernel_kinds(mamba)
    assert kinds.count("_ssd_fwd") == 9 and kinds.count("_ssd_bwd") == 9
    scans = [line for line in mamba if "_ssd_" in line]
    assert all("/mamba/scan/" in line for line in scans)
    assert all("bf16[1,8192,4096]" in line and "bf16[1,8192,128]" in line
               for line in scans)
    # dB, dC: a float32 share a head block of the one group
    assert all("f32[8,1,8192,128]" in line for line in scans
               if "_ssd_bwd" in line)
    assert all(re.search(r"/h_5/attn/core/", line) for line in attn)
    assert sorted(kernel_kinds(attn)) == ["_flash_bwd", "_flash_fwd"]
    assert all("bf16[1,8192,2048]" in line for line in attn)
    assert len(head) == 1 and "25088" in head[0]
    # no [T, T] score array (the MLP's halves are [8192, 8192] bfloat16)
    assert "8192x8192xf32" not in lowered.as_text()


@pytest.mark.parametrize("scope, made_again", [
    ("mamba/in_proj", False), ("mamba/out_proj", False),
    ("attn/out/o", False), ("attn/qkv/q", True), ("mlp/gate_up", False)],
    ids=["mamba_in_proj", "mamba_out_proj", "attn_o", "attn_q_is_again",
         "mlp_gate_up"])
def test_no_projection_of_a_mixer_is_left_in_the_second_pass(
        real_size_step, scope, made_again):
    """The lowered step's ``dot_general``s by scope path: under
    ``rematted_computation`` (a recomputed block's second forward pass)
    there is none of ``in_proj`` or ``out_proj`` (the parts of
    ``in_proj``'s product and the stream after the mixer are kept by
    name), none of the attention layer's ``o`` and none of ``gate_up``;
    ``q`` is projected again, which says the search finds what is
    there. Each has its forward and two backward matmuls a layer."""
    _, _, lowered = real_size_step
    text = lowered.as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    dots = [names[loc] for loc in re.findall(
        r"stablehlo\.dot_general.* loc\((#loc\d+)\)$", text, re.M)]
    here = [path for path in dots if f"/{scope}/" in path]
    layers = 1 if scope.startswith("attn") else 9 + scope.startswith("mlp")
    again = [path for path in here if "rematted_computation" in path]
    assert len(again) == (layers if made_again else 0), again
    assert len(here) - len(again) == 3 * layers


def test_the_scans_kernels_compile_for_v5e_at_one_group_and_chunk_256(v5e):
    """Mosaic fits both kernels at the cell's shape (one sequence of
    8,192 rows, 64 heads of 64, state 128 in **one** group, chunks of
    **256**: five ``[256, 256]`` float32 squares a head of a block in
    the backward's phases) in scoped VMEM on a v5e, with no
    ``vmem_limit_bytes``; the temporaries are the entering states and
    the eight float32 shares of ``dB`` and ``dC``."""
    from ray_tpu.ops.pallas import ssd_scan
    arg = on_device(v5e[0])

    def loss(*a):
        return ssd_scan.ssd_scan(*a, chunk=256).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        arg((1, 8192, 64, 64), jnp.bfloat16), arg((1, 8192, 64), jnp.float32),
        arg((64,), jnp.float32), arg((1, 8192, 1, 128), jnp.bfloat16),
        arg((1, 8192, 1, 128), jnp.bfloat16), arg((64,), jnp.float32)
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.slow
def test_the_real_size_step_compiles_inside_the_chips_memory(real_size_step):
    """The step's peak, ``memory_analysis().peak_memory_in_bytes``,
    fills the chip and fits it: between 14.5 and 16.0 GB of the 16.909
    (15.75 GiB; 14.75 read, 13.15 before ``in_proj``'s parts and the
    stream after the mixer were kept), with the scan's two names, the
    attention core's two, the stream, ``in_proj``'s three and
    ``gate_up`` kept on every layer. Arguments are 10 bytes a
    parameter (float32 parameter, bfloat16 and float32 moments). No
    fusion of the compiled step is XLA's own rematerialisation
    (``.remat`` in its name), which is what a step pays with when it is
    asked to keep more than fits."""
    cfg, _, lowered = real_size_step
    compiled = lowered.compile()
    m, total = program_bytes(compiled)
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)
    print(f"granite step: peak {m.peak_memory_in_bytes / 1e9:.2f} GB, "
          f"arguments + temporaries {total / 1e9:.2f}, arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f}, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f}")
    assert 14.5e9 < m.peak_memory_in_bytes < 16.0e9
    assert not re.findall(r"^\s+%?[\w.\-]*\.remat\d* = ", compiled.as_text(),
                          re.M)

"""The Xing4.0 cell's new pieces compile for the real chip, with no chip
here (as ``test_tpu_compile_joyai.py``: the TPU compiler for a described
v5e; nothing runs, so nothing here is a result or a time). The real-size
step is lowered once for every assertion on it, in tier-1; the TPU compiler
takes it on demand (``-m slow``).

**The reading that decided the cell's memory step** (``memory_analysis()``
of the step below, PR 54): with the MTP module 913.5 M parameters compile
to 14.73 GB (arguments 9.14, temporaries 5.59, of which 3.65 are the
gradients), over the 14.6 GB the cell allows itself of the chip's 15.75;
halving the loss's chunk leaves it where it is. Without the module, which
is what the cell runs and this file compiles: 759.3 M, 12.03 GB
(arguments 7.60, temporaries 4.43)."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from conftest import (  # noqa: E402
    kernel_calls, kernel_kinds, lower_real_size_step, on_device,
    program_bytes, router_choice_calls)

T = 4096        # the cell's row: rope_scaling's original length


def _cell_config():
    from ray_tpu.models.joyai import JoyAIConfig
    return JoyAIConfig.xing4_0_29b_a4b(
        n_layer=5, dense_layers=1, experts_held=(0, 8), vocab_size=16384,
        mtp_depth=0, remat=True)


def test_latent_attention_kernels_compile_at_4096_rows_with_a_scale(v5e):
    """One sequence of 4,096 tokens, 32 heads of 128 + 64 against values
    of 128, at YaRN's scale (0.14468, a static argument of both
    kernels): two custom calls, no ``[H, T, T]`` array, and the scale is
    the compiled kernels' and not the default's."""
    from ray_tpu.ops.pallas.flash_attention import (
        mla_flash_core, mla_flash_static)
    arg = on_device(v5e[0])
    scale = _cell_config().mla_scale
    static = mla_flash_static(T, 128, 64, scale)
    assert static.scale == pytest.approx(0.144680, rel=1e-5)
    assert mla_flash_static(T, 128, 64).scale == pytest.approx(192 ** -0.5)

    def loss(*operands):
        return mla_flash_core(*operands, static).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg((1, T, 32 * 128), jnp.bfloat16),
        arg((1, T, 32 * 64), jnp.bfloat16),
        arg((1, T, 32 * 128), jnp.bfloat16),
        arg((1, T, 64), jnp.bfloat16),
        arg((1, T, 32 * 128), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # no score matrix ([1, T, 32 x 128] is [1, 4096, 4096] here: the
    # heads' own dimension tells them apart)
    assert f"32,{T},{T}" not in text and f"{T},32,{T}" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_held_experts_compile_at_a_hidden_size_of_3584(v5e, monkeypatch):
    """The cell's routed layer (4,096 tokens, 64 sigmoid-routed SwiGLU
    experts of 1,024 of which 8 are held, top-4) at ``k`` = 3,584 = 7 x
    512, no power of two: Mosaic takes the grouped matmuls at the tile
    ``ops/moe.py`` chooses (nine custom calls: three matrices, each
    forward, for its input and for its weights)."""
    from ray_tpu.ops import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.grouped_matmul_path() == "megablox_gmm"
    arg = on_device(v5e[0])

    def loss(x, router, bias, gate, up, down):
        y, _, _, _ = moe.routed_ffn(
            x, router, gate, up, down, top_k=4, norm_topk_prob=True,
            router="sigmoid", select_bias=bias, route_scale=2.0,
            expert="swiglu", experts_held=(0, 8))
        return y.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        arg((1, T, 3584), jnp.bfloat16), arg((3584, 64), jnp.float32),
        arg((64,), jnp.float32), arg((8, 3584, 1024), jnp.float32),
        arg((8, 3584, 1024), jnp.float32),
        arg((8, 1024, 3584), jnp.float32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 9


@pytest.fixture(scope="module")
def real_size_step(v5e):
    """The cell's step as the builder makes it (one dense and four routed
    layers with 8 of 64 experts held, four residual streams, 16,384 rows
    of both tables, adamw with a bf16 first moment, 4,096 tokens, blocks
    recomputed), lowered once: (config, the trace's notes, the lowered
    program)."""
    from ray_tpu.models.joyai import JoyAI, joyai_loss_fn
    cfg = _cell_config()
    model = JoyAI(cfg)
    return cfg, *lower_real_size_step(
        v5e[0], model, joyai_loss_fn(model, ce_chunk=2048),
        (1, cfg.seq_len),
        grad_groups={"grad_norm_hc": r"(^|/)hc_(attn|mlp)/(phi|b|alpha)$"})


@pytest.mark.slow
def test_the_real_size_step_compiles_inside_the_chips_memory(real_size_step):
    """Arguments + temporaries + unaliased outputs are inside the chip's
    16.909 GB (15.75 GiB) less the ~2.7 GB the MTP module would take and
    the cell leaves free for it (12.03 GB at PR 54; the docstring above
    has the reading with the MTP module; 11.86 since PR 68). **PR 70's
    11.74** (``peak_memory_in_bytes`` 10.71 -> 10.84): 0.63 GB of arrays
    kept by name, each sub-layer's output as ``post`` reads it
    (``out_proj``'s product, ``down``'s, the held experts' sum: 29 MB
    each) and the dense and shared MLPs' ``gate`` and ``up`` (0.15 GB and
    17 MB a layer), for *less* program than without them (the step's peak
    is a block's backward, which held that block's products already), of
    the 1.5 GB the issue allowed the cell. No fusion is XLA's own
    rematerialisation (``.remat`` in its name), and the second pass holds
    no matmul but the two down projections' (``q_down``, ``kv_down``: 11
    MB a layer would keep them). And what
    only the compiled program says of the n-stream state: it is ``[1, T,
    n d]`` in bfloat16 everywhere, never float32 at that width, never
    with the 4 streams second-minor."""
    cfg, _, lowered = real_size_step
    compiled = lowered.compile()
    m, total = program_bytes(compiled)
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)    # f32 + bf16 + f32 a parameter
    assert 11.3e9 < total <= 11.74e9 + 0.1e9 < 16.909e9 - 2.7e9
    assert m.peak_memory_in_bytes <= 10.84e9 + 0.1e9
    # the buffers are the entry computation's results (inside a fusion a
    # float32 value of the state's width lives in registers)
    text = compiled.as_text()
    assert not re.findall(r"^\s+%?[\w.\-]*\.remat\d* = ", text, re.M)
    again = set(re.findall(
        r"rematted_computation/h_\d/(\w+/\w+)/(?:proj/)?dot_general", text))
    assert again <= {"attn/q_down", "attn/kv_down"}, again
    assert "rematted_computation/h_1/mlp/cond" not in text
    entry = text[text.rindex("\nENTRY "):]
    wide = cfg.hc_mult * cfg.n_embd
    assert f"bf16[1,{T},{wide}]" in entry
    assert f"f32[1,{T},{wide}]" not in entry
    assert f"f32[{T},{wide}]" not in entry
    assert not re.search(rf"\[(1,)?{T},{cfg.hc_mult},{cfg.n_embd}\]", entry)


def test_the_real_size_step_takes_the_kernels_it_should(real_size_step):
    """Every layer's attention is the kernel, forward once (a recomputed
    block keeps its results) and backward once; the head's forward is
    one custom call under ``loss``."""
    cfg, _, lowered = real_size_step
    assert cfg.num_params() == pytest.approx(759.3e6, rel=1e-4)
    calls = kernel_calls(lowered)
    flash = [line for line in calls if "/attn/core/" in line]
    assert sum("mla_flash_fwd" in line for line in flash) == 5
    assert sum("mla_flash_bwd" in line for line in flash) == 5
    head = [line for line in calls if "jit(_ce_lse_fwd)" in line]
    assert len(head) == 1 and "/loss/" in head[0]
    # the four routers' choice: the kernel pair once a layer (the
    # recomputed blocks keep the routers' names since PR 68), no
    # ``top_k`` or gather left
    router_choice_calls(lowered, 4, "f32[64,4096]", "i32[4,4096]")


def test_the_real_size_steps_residual_maps_are_one_kernel_pair_a_sublayer(
        real_size_step):
    """Under every ``h_i/hc_attn/maps`` and ``h_i/hc_mlp/maps`` one
    forward call (the recomputed block keeps its five results and makes
    none again) and one backward call, over ``[24, 32, 128]``: the
    4,096 tokens in the lanes, whole vector registers."""
    cfg, _, lowered = real_size_step
    maps = [line for line in kernel_calls(lowered)
            if re.search(r"/h_\d/hc_(attn|mlp)/maps/", line)]
    assert sorted(kernel_kinds(maps)) == (
        ["_hc_maps_bwd"] * 10 + ["_hc_maps_fwd"] * 10)
    for layer in range(cfg.n_layer):
        for sub in ("attn", "mlp"):
            here = [line for line in maps
                    if f"/h_{layer}/hc_{sub}/maps/" in line]
            assert sorted(kernel_kinds(here)) == [
                "_hc_maps_bwd", "_hc_maps_fwd"], (layer, sub)
    assert all(f"f32[24,{T // 128},128]" in line for line in maps)


def test_the_real_size_step_says_what_it_ran(real_size_step):
    cfg, notes, _ = real_size_step
    assert {k: notes[k] for k in (
        "flash_path", "flash_layout", "mla_saved", "flash_bwd_resident_rows",
        "rope_kind", "hc_mult", "hc_sinkhorn_iters", "hc_state_dtype",
        "hc_maps_path", "hc_maps_block", "blocks_remat",
        "blocks_remat_keeps", "moe_path", "moe_experts_held",
        "moe_router_path")} == {
        "flash_path": "mla_multi_block", "flash_layout": "bthd",
        "mla_saved": "latents", "flash_bwd_resident_rows": T,
        "rope_kind": "yarn", "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_state_dtype": "bfloat16", "hc_maps_path": "pallas",
        "hc_maps_block": 1024, "blocks_remat": True,
        "blocks_remat_keeps": "moe_router_logits,moe_router_experts,"
                              "moe_router_weights,moe_router_counts,"
                              "moe_router_lse,hc_maps_pre,hc_maps_post,"
                              "hc_maps_res,hc_maps_m,hc_maps_r,"
                              "mixer_out_proj,mlp_down,moe_routed_out,"
                              "mlp_gate,mlp_up,attn_out,attn_lse",
        "moe_path": "megablox_gmm", "moe_experts_held": [0, 8],
        "moe_router_path": "pallas"}
    assert notes["mla_scale"] == pytest.approx(cfg.mla_scale)


def test_the_real_size_steps_kernels_and_layouts(real_size_step):
    """No ``[H, T, T]`` array exists; the n-stream state enters and leaves
    the blocks as ``[1, T, n d]`` in bfloat16; and the maps' scopes reach
    the program's op names."""
    cfg, _, lowered = real_size_step
    text = lowered.as_text(debug_info=True)
    assert f"32x{T}x{T}" not in text and f"{T}x32x{T}" not in text
    assert f"tensor<1x{T}x{cfg.hc_mult * cfg.n_embd}xbf16>" in text
    for scope in ("h_4/hc_attn/maps", "h_4/hc_mlp/post", "h_0/hc_mlp/pre",
                  "embed/hc_expand", "blocks/hc_collapse"):
        assert scope in text, scope


def test_rotated_latent_attention_under_a_recomputed_block():
    """The kernels' path (interpreted, on the CPU) inside
    ``jax.checkpoint`` with the rotation's angles made outside it, as
    ``nn.remat`` hands them to a block: the angles are an operand of the
    attention's custom gradient, not a closed-over tracer of a trace
    that has ended when the backward rule is traced (which raised
    ``UnexpectedTracerError`` before PR 54; no earlier model rotated
    latent attention inside a recomputed block). Gradients are those of
    the kernels' own rule."""
    from ray_tpu.models.llama import rope_freqs
    from ray_tpu.ops import mla
    t, h = 128, 2
    ks = jax.random.split(jax.random.key(0), 6)
    up = mla.UpProjections(*(jax.random.normal(k, (16, h * w)) * 0.1
                             for k, w in zip(ks, (128, 64, 128, 128))))
    c = jax.random.normal(ks[4], (1, t, 16))
    k_r = jax.random.normal(ks[5], (1, t, 64))

    def attend(c, up, angles, **kw):
        return mla.latent_attention(c, c, k_r, up, angles, n_head=h,
                                    interpret=True, scale=0.1, **kw).sum()

    def recomputed(c, up):
        return jax.checkpoint(attend)(c, up, rope_freqs(64, t, 1e4))

    def plain(c, up):
        return attend(c, up, rope_freqs(64, t, 1e4), saved="expanded")

    got = jax.jit(jax.grad(recomputed, argnums=(0, 1)))(c, up)
    want = jax.jit(jax.grad(plain, argnums=(0, 1)))(c, up)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(g - w).max()) <= 1e-5 * float(jnp.abs(w).max())

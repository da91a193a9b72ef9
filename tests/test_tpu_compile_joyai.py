"""The JoyAI-LLM-Flash cell's new pieces compile for the real chip, with
no chip here (as ``test_tpu_compile_nemotron.py``: the TPU compiler for a
described v5e; nothing runs, so nothing here is a result or a time)."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from conftest import (  # noqa: E402
    kernel_calls, lower_real_size_step, on_device, program_bytes,
    router_choice_calls)


def _compiled_kernels(device, t, heads=32):
    """Forward and backward of the latent-attention kernels at ``t``
    rows, compiled for ``device``; with them the notes of the path."""
    from ray_tpu.ops.pallas.flash_attention import (
        mla_flash_core, mla_flash_static)
    arg = on_device(device)
    static = mla_flash_static(t, 128, 64)

    def loss(*operands):
        return mla_flash_core(*operands, static).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg((1, t, heads * 128), jnp.bfloat16),
        arg((1, t, heads * 64), jnp.bfloat16),
        arg((1, t, heads * 128), jnp.bfloat16),
        arg((1, t, 64), jnp.bfloat16),
        arg((1, t, heads * 128), jnp.bfloat16)).compile()
    return static, compiled, compiled.as_text()


def test_latent_attention_kernels_compile_for_v5e_at_the_cells_shape(v5e):
    """One sequence of 8,192 tokens, 32 heads of 128 + 64 against values
    of 128, in bfloat16, forward and backward: **two** custom calls (the
    backward pass is one kernel, and the compile succeeding is the proof
    that its accumulators fit the VMEM limit it asks for), no ``[T, T]``
    array and no operand 256 wide (the rotary key is the one ``[1, 8192,
    64]`` array it is), temporaries a fraction of a GB."""
    _, compiled, text = _compiled_kernels(v5e[0], 8192)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "8192,8192" not in text
    assert "8192,32,256" not in text and "8192,32,192" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("t, heads, fits", [(32768, 8, True),
                                            (65536, 4, False)],
                         ids=["the_longest_row_that_fits", "one_too_long"])
def test_the_backward_kernel_compiles_where_its_accumulators_fit(
        v5e, t, heads, fits):
    """``_mla_bwd_fits`` counts from the shapes; the compiler has the last
    word. 32,768 rows (64 MiB of resident accumulators under the 100 MiB
    the kernel asks for) compile, forward and backward two custom calls;
    65,536 are refused by name before anything is lowered."""
    if not fits:
        with pytest.raises(NotImplementedError, match=f"{t} rows"):
            _compiled_kernels(v5e[0], t, heads)
        return
    _, _, text = _compiled_kernels(v5e[0], t, heads)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert f"{t},{t}" not in text


@pytest.fixture(scope="module")
def real_size_step(v5e):
    """The cell's step as the builder makes it (the dense layer, four
    routed layers with 16 of 256 experts held, the MTP module, 16,384
    rows of the vocabulary; adamw with a bf16 first moment) at 8,192
    tokens, lowered once: (config, the trace's notes, the lowered
    program)."""
    from ray_tpu.models.joyai import JoyAI, JoyAIConfig, joyai_loss_fn
    cfg = JoyAIConfig.joyai_llm_flash(n_layer=5, experts_held=(0, 16),
                                      vocab_size=16384)
    model = JoyAI(cfg)
    return cfg, *lower_real_size_step(
        v5e[0], model, joyai_loss_fn(model, ce_chunk=2048),
        (1, cfg.seq_len))


def test_the_real_size_step_takes_the_kernels_it_should(real_size_step):
    """Every layer's attention is the kernel (a forward and ONE backward
    custom call a layer beside the experts'), and no ``[T, T]`` array
    exists."""
    _, notes, lowered = real_size_step
    assert notes["flash_path"] == "mla_multi_block"
    assert notes["flash_layout"] == "bthd"
    assert notes["mla_saved"] == "latents"
    assert notes["flash_bwd_resident_rows"] == 8192
    calls = kernel_calls(lowered)
    assert sum("mla_flash_fwd" in line for line in calls) >= 6
    assert sum("mla_flash_bwd" in line for line in calls) == 6
    # the head's forward (PR 51), once for the loss and once for the MTP
    # module's pass over the same table
    head = [line for line in calls if "jit(_ce_lse_fwd)" in line]
    assert len(head) == 2 and all("/loss/" in line for line in head)
    assert sum(bool(re.search(r"loss\)?/mtp/", line)) for line in head) == 1
    # the routers' choice (four layers and the MTP module's block): the
    # kernel pair once a layer, no ``top_k`` or gather left
    assert notes["moe_router_path"] == "pallas"
    router_choice_calls(lowered, 5, "f32[256,8192]", "i32[8,8192]")
    assert "8192x8192" not in lowered.as_text()


@pytest.mark.slow
def test_the_real_size_step_compiles_inside_the_chips_memory(real_size_step):
    """Arguments + temporaries + unaliased outputs fit the v5e's 15.75 GB
    with the room the acceptance asks for."""
    cfg, _, lowered = real_size_step
    m, total = program_bytes(lowered.compile())
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)    # f32 + bf16 + f32 a parameter
    assert total < 15.2e9


def _dp(v5e):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(v5e), ("dp",))
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    whole = NamedSharding(mesh, PartitionSpec())
    return mesh, (lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=rows)), (
        lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=whole))


def _narrow(**kw):
    """Published head widths (128 + 64 against 128), little else."""
    from ray_tpu.models.joyai import JoyAIConfig
    return JoyAIConfig.tiny(**{**dict(
        n_layer=2, n_embd=128, seq_len=256, n_head=2, q_rank=64, kv_rank=32,
        nope_dim=128, rope_dim=64, v_dim=128), **kw})


def test_a_model_compiles_for_four_chips_with_the_batch_over_dp(
        v5e, monkeypatch):
    """Given the mesh, latent attention's kernels run under a
    ``shard_map`` over ``dp`` (a ``pallas_call`` has no SPMD rule), a
    custom call a pass on each chip, and no chip gathers another's rows;
    given none in a process of several devices, it fails loudly and does
    not fall to the XLA attention."""
    from ray_tpu.models.joyai import JoyAI, joyai_loss_fn
    from ray_tpu.util import tracing
    assert jax.device_count() > 1
    mesh, rows, whole = _dp(v5e)
    cfg = _narrow()
    params = jax.tree.map(
        lambda z: whole(z.shape, z.dtype),
        jax.eval_shape(JoyAI(cfg).init_params, jax.random.key(0)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    batch = {k: rows((4, cfg.seq_len), jnp.int32)
             for k in ("tokens", "targets")}

    def grads(model):
        loss = joyai_loss_fn(model, ce_chunk=64)
        return jax.jit(jax.grad(lambda p, b: loss(p, b)[0])).lower(
            params, batch)

    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    text = grads(JoyAI(cfg, mesh=mesh)).compile().as_text()
    assert notes["flash_path"] == "mla_multi_block"
    assert text.count("mla_flash_fwd") >= 1 and "all-gather" not in text
    with pytest.raises(NotImplementedError, match="no mesh"):
        grads(JoyAI(cfg))


def test_layers_at_one_shape_trace_each_kernel_once(monkeypatch):
    """What holds ``setup_s`` (PERF.md section 6, PR 28): the two
    functions that hold the ``pallas_call``s are jitted, so a step's
    three blocks trace each kernel's body once and lower to one function
    a pass (the backward pass one kernel), called three times. Interpreted, on the CPU: counting traces
    needs nothing of the chip."""
    from ray_tpu.models.llama import rope_freqs
    from ray_tpu.ops import mla
    import importlib
    # the package exports the function under the module's name
    fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")
    bodies = []
    scores = fa._mla_scores
    monkeypatch.setattr(fa, "_mla_scores",
                        lambda *a: bodies.append(1) or scores(*a))
    t, h = 128, 2           # no other test's shape
    ks = jax.random.split(jax.random.key(0), 7)
    up = mla.UpProjections(*(jax.random.normal(k, (16, h * w)) * 0.1
                             for k, w in zip(ks, (128, 64, 128, 128))))
    c = jax.random.normal(ks[4], (1, t, 16))
    k_r = jax.random.normal(ks[5], (1, t, 64))
    angles = rope_freqs(64, t, 1e4)

    def three_layers(c, up):
        y = 0.0
        for i in range(3):
            with jax.named_scope(f"h_{i}"):
                y = y + mla.latent_attention(
                    c, c, k_r, up, angles, n_head=h, interpret=True).sum()
        return y

    text = jax.jit(jax.grad(three_layers, argnums=(0, 1))).lower(
        c, up).as_text()
    assert text.count("call @mla_flash_fwd") == 3
    assert text.count("call @mla_flash_bwd") == 3
    # one head pair a cell, two heads a pair; each body branches on the
    # diagonal: forward 2 x 2, backward 2 x 2 score products
    assert len(bodies) == 8

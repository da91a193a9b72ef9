"""The SmallThinker cell's step compiles for the real chip, with no chip
here (as ``test_tpu_compile_zaya.py``: the TPU compiler for a described
v5e; nothing runs, so nothing here is a result or a time)."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


@pytest.fixture(scope="module")
def v5e():
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, no description
        pytest.skip(f"cannot describe a v5e:2x2 here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_the_real_size_step_compiles_inside_the_chips_memory(
        v5e, monkeypatch):
    """The cell's step as the builder makes it (one period of four
    layers with 16 of 64 experts held, 19,072 rows of each table; adamw
    with a bf16 first moment) at 1 x 16,384 tokens: arguments +
    temporaries + unaliased outputs stay under the 15.0 GB at which the
    configuration file's ``cut.memory`` would have turned to ``remat``,
    every layer's attention is the equal-width multi-block kernel, the
    windowed layers' under a window of 4,096 with the band's 70 block
    pairs a head and not the causal grid's 136, its backward pass ONE
    kernel a layer with dq's 16,384 rows resident (four ``_flash_bwd``
    custom calls), each kernel's
    call under its layer's scope (``attn/core`` in layer 0,
    ``attn/window`` in layers 1-3), and no ``[T, T]`` array exists."""
    import re

    import optax

    from ray_tpu import train
    from ray_tpu.models.smallthinker import (
        SmallThinker,
        SmallThinkerConfig,
        smallthinker_loss_fn,
    )
    from ray_tpu.util import tracing
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)   # the cell's chip
    one = SingleDeviceSharding(v5e[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = SmallThinkerConfig.smallthinker_21b_a3b(
        n_layer=4, experts_held=(0, 16), vocab_size=19072)
    model = SmallThinker(cfg)
    opt = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(2e-5, b1=0.9, b2=0.95, weight_decay=0.1,
                    mu_dtype=jnp.bfloat16))
    step = train.make_train_step(
        smallthinker_loss_fn(model, ce_chunk=2048), opt)
    state = jax.tree.map(
        lambda z: arg(z.shape, z.dtype),
        jax.eval_shape(lambda: train.init_train_state(
            model.init_params(jax.random.key(0)), opt, None)))
    batch = {k: arg((1, cfg.seq_len), jnp.int32)
             for k in ("tokens", "targets")}
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    compiled = step.lower(state, batch).compile()
    assert notes["flash_path"] == "multi_block"
    assert notes["flash_layout"] == "bthd"
    assert notes["flash_window"] == 4096
    assert notes["flash_band_blocks"] == 70 < 16 * 17 // 2
    assert notes["flash_bwd_resident_rows"] == 16384
    assert notes["attn_kind"] == "window_global"
    assert notes["attn_layers"] == "gWWW"
    assert notes["moe_router_input"] == "pre_attention"
    assert notes["moe_router"] == "caller" and notes["moe_top_k"] == 6
    assert notes["moe_expert_kind"] == "reglu"
    assert notes["moe_experts_held"] == [0, 16]
    assert notes["moe_rows_sorted"] == 49152    # twice the even share
    assert notes["moe_path"] == "megablox_gmm"
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + max(0, m.output_size_in_bytes - m.alias_size_in_bytes))
    print(f"program {total / 1e9:.2f} GB: arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f}, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f}")
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)    # f32 + bf16 + f32 a parameter
    assert 0.25 * 15.75e9 < total <= 15.0e9
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kinds = [re.search(r"jit\((\w+)\)/pallas_call", line).group(1)
             for line in calls]
    assert set(kinds) == {"_flash_fwd", "_flash_bwd", "gmm", "tgmm",
                          "_ce_lse_fwd"}
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
    # sum on the plain path), and neither scope holds a scatter
    assert notes["moe_rows_path"] == "tgmm"
    sums = [line for kind, line in zip(kinds, calls)
            if kind == "tgmm" and "jit(_sum)" in line]
    assert not any("/while/body/" in line for line in sums)
    assert sum("/mlp/combine/jit(_sum)/jit(tgmm)" in line for line in sums) == 4
    assert sum(bool(re.search(r"/mlp/\S*dispatch\S*/jit\(_sum\)/jit\(tgmm\)",
                              line)) for line in sums) == 4
    assert not [line for line in text.splitlines()
                if " scatter(" in line and re.search(
                    r"/mlp/[^ \"]*(dispatch|combine)", line)]
    print("VMEM of the sums' tgmm:", sorted({
        re.search(r'used_scoped_memory_configs[^]]*?"size":"(\d+)"', line)
        .group(1) for line in sums if "experts" not in line}))
    assert "16384,16384" not in text

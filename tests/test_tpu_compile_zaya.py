"""The ZAYA1-8B cell's step compiles for the real chip, with no chip here
(as ``test_tpu_compile_joyai.py``: the TPU compiler for a described v5e;
nothing runs, so nothing here is a result or a time)."""

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
    """The cell's step as the builder makes it (five layers with 8 of
    16 experts held, 32,896 rows of the tied table; adamw with a bf16
    first moment) at 2 x 8,192 tokens: arguments + temporaries +
    unaliased outputs stay under the 15.0 GB at which the configuration
    file's ``cut.memory`` would have gone to four layers and at no more
    than the 13.17 GB of the step before CCA's kernels, every layer's
    attention is the equal-width multi-block kernel with ONE backward
    kernel a layer (dq's 8,192 rows resident), CCA's passes in
    front of it are ``ops/pallas/cca_mix.py``'s pair (two kinds of
    custom call beside the flash kernels' two and the grouped matmuls'
    two, one of each a layer, under ``attn/mix``), the loss's forward is
    ``ops/pallas/ce_lse.py``'s one call, and no ``[T, T]`` array
    exists."""
    import re

    import optax

    from ray_tpu import train
    from ray_tpu.models.zaya import Zaya, ZayaConfig, zaya_loss_fn
    from ray_tpu.util import tracing
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)   # the cell's chip
    one = SingleDeviceSharding(v5e[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = ZayaConfig.zaya1_8b(n_layer=5, experts_held=(0, 8),
                              vocab_size=32896)
    model = Zaya(cfg)
    opt = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(2e-5, b1=0.9, b2=0.95, weight_decay=0.1,
                    mu_dtype=jnp.bfloat16))
    step = train.make_train_step(zaya_loss_fn(model, ce_chunk=2048), opt)
    state = jax.tree.map(
        lambda z: arg(z.shape, z.dtype),
        jax.eval_shape(lambda: train.init_train_state(
            model.init_params(jax.random.key(0)), opt, None)))
    batch = {k: arg((2, cfg.seq_len), jnp.int32)
             for k in ("tokens", "targets")}
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    compiled = step.lower(state, batch).compile()
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
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + max(0, m.output_size_in_bytes - m.alias_size_in_bytes))
    assert m.argument_size_in_bytes == pytest.approx(
        cfg.num_params() * 10, rel=1e-3)    # f32 + bf16 + f32 a parameter
    assert 0.25 * 15.75e9 < total <= 13.17e9
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kinds = [re.search(r"jit\((\w+)\)/pallas_call", line).group(1)
             for line in calls]
    assert set(kinds) == {"_flash_fwd", "_flash_bwd", "gmm", "tgmm",
                          "_mix_fwd", "_mix_bwd", "_ce_lse_fwd"}
    # the head's forward (PR 51): one kernel over the 16,384 rows under
    # ``loss``, no scan, and no pass over float32 logits behind it
    assert notes["ce_path"] == "pallas_lse"
    assert [("/loss/" in line, "/while/" in line) for kind, line
            in zip(kinds, calls) if kind == "_ce_lse_fwd"] == [(True, False)]
    assert "exponential_reduce" not in text
    assert kinds.count("_mix_fwd") == kinds.count("_mix_bwd") == 5
    assert kinds.count("_flash_fwd") == 5
    assert kinds.count("_flash_bwd") == 5       # one kernel a layer
    assert all("/attn/mix/jit(_mix_" in line
               for kind, line in zip(kinds, calls) if "_mix_" in kind)
    # the routed layer's row moves (PR 43): each token's sum is a gather
    # and a tgmm under combine, its transpose the same under the call's
    # scope, dispatch (a layer's first slab; the loops over further slabs
    # sum on the plain path), and neither scope holds a scatter
    assert notes["moe_rows_path"] == "tgmm"
    sums = [line for kind, line in zip(kinds, calls)
            if kind == "tgmm" and "jit(_sum)" in line]
    assert not any("/while/body/" in line for line in sums)
    assert sum("/mlp/combine/jit(_sum)/jit(tgmm)" in line for line in sums) == 5
    assert sum(bool(re.search(r"/mlp/\S*dispatch\S*/jit\(_sum\)/jit\(tgmm\)",
                              line)) for line in sums) == 5
    assert not [line for line in text.splitlines()
                if " scatter(" in line and re.search(
                    r"/mlp/[^ \"]*(dispatch|combine)", line)]
    print("VMEM of the sums' tgmm:", sorted({
        re.search(r'used_scoped_memory_configs[^]]*?"size":"(\d+)"', line)
        .group(1) for line in sums if "experts" not in line}))
    assert "8192,8192" not in text

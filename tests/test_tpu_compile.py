"""The main path's kernels compile for the real chip, with no chip here.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described and not attached (on-chip-measurement guide, §2,
third rehearsal): it refuses what interpret mode lets through — a
slice off the tiling, too much VMEM, a kernel that cannot be
partitioned. Nothing runs, so nothing here is a result or a time.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

from ray_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402

# [batch, seq, heads, head_dim]: chip_smoke.py's GPT-2 124M shape, one
# head-dim-128 shape (multi-block path: 2048 = 2 x 1024 blocks), and the
# OLMoE cell's (olmoe-1b-7b.b4-t4096: four 1,024-blocks at D=128).
SHAPES = [(32, 1024, 12, 64), (8, 2048, 32, 128), (4, 4096, 16, 128)]


@pytest.fixture(scope="module")
def v5e():
    """The four described chips of a v5e:2x2, persistent cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without the chip (it would warn and recompile)."""
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


def _loss(q, k, v):
    return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape", SHAPES,
                         ids=["x".join(map(str, s)) for s in SHAPES])
def test_flash_kernel_compiles_for_v5e(v5e, shape, grad):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))
    fn = jax.grad(_loss, argnums=(0, 1, 2)) if grad else _loss
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_devices", [4, 1], ids=["dp4", "one_of_four"])
def test_mesh_dispatch_keeps_the_kernel(v5e, monkeypatch, n_devices):
    """What a model given a mesh dispatches: on dp=4 the kernel under
    shard_map, batch over four chips; on a one-device mesh of a
    four-chip host the bare kernel — decided from the mesh, while this
    process counts eight devices. The dispatch asks
    jax.default_backend(); steered here, in the test."""
    from ray_tpu.ops.attention import make_sharded_causal_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.device_count() > 1
    mesh = Mesh(v5e[:n_devices], ("dp",))
    attn = make_sharded_causal_attention(mesh)
    x = jax.ShapeDtypeStruct(SHAPES[0], jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))
    text = jax.jit(attn).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("family", ["gpt2", "llama", "moe"])
def test_every_model_takes_its_attention_from_the_mesh(
        v5e, monkeypatch, family):
    """No model file decides the kernel for itself: each one, given a
    one-device mesh on a many-device host, compiles the kernel."""
    from ray_tpu import models

    cls, cfg = {"gpt2": (models.GPT2, models.GPT2Config),
                "llama": (models.Llama, models.LlamaConfig),
                "moe": (models.MoETransformer, models.MoEConfig)}[family]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(v5e[:1], ("dp",))
    attn = cls(cfg.tiny(), mesh=mesh)._attn_fn()
    x = jax.ShapeDtypeStruct(SHAPES[0], jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    text = jax.jit(attn).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text


def test_routed_experts_compile_for_v5e(v5e, monkeypatch):
    """The OLMoE cell's routed layer at the published widths (16,384
    tokens, 64 experts x 1,024, top-8), forward and backward: on a TPU
    the grouped matmuls are the megablox Pallas kernel, which Mosaic has
    to take at the tile ``ops/moe.py`` chose (nine custom calls: three
    matrices, each forward, for its input and for its weights)."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.grouped_matmul_path() == "megablox_gmm"
    one = SingleDeviceSharding(v5e[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(x, router, gate, up, down):
        y, aux, z, _ = moe.routed_ffn(x, router, gate, up, down, top_k=8)
        return y.astype(jnp.float32).sum() + aux + z

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg((4, 4096, 2048), jnp.bfloat16), arg((2048, 64), jnp.float32),
        arg((64, 2048, 1024), jnp.float32), arg((64, 2048, 1024), jnp.float32),
        arg((64, 1024, 2048), jnp.float32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 9

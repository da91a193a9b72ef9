"""The Nemotron-H cell's new pieces compile for the real chip, with no
chip here (as ``test_tpu_compile.py``: the TPU compiler for a described
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


def _arg(device):
    one = SingleDeviceSharding(device)
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def test_held_experts_compile_for_v5e_at_the_cells_widths(v5e, monkeypatch):
    """8 of 128 relu^2 experts at 2,688 x 1,856 (neither a multiple of
    the kernel's 1,024 tile; 1,856 is 14.5 lane tiles) over a slab of
    6,144 of the 49,152 sorted routes, forward and backward: six
    megablox custom calls (two matrices, each forward, for its input
    and for its weights) inside the slab loop."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.held_rows(8192 * 6, 8, 128) == 6144
    arg = _arg(v5e[0])

    def loss(x, router, up, down, bias):
        y, _, _, load = moe.routed_ffn(
            x, router, None, up, down, top_k=6, norm_topk_prob=True,
            router="sigmoid", select_bias=bias, route_scale=2.5,
            expert="relu2", experts_held=(0, 8))
        return y.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        arg((1, 8192, 2688), jnp.bfloat16), arg((2688, 128), jnp.float32),
        arg((8, 2688, 1856), jnp.float32), arg((8, 1856, 2688), jnp.float32),
        arg((128,), jnp.float32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 6


def test_chunked_scan_compiles_for_v5e_at_the_cells_shape(v5e):
    """One sequence of 8,192 tokens, 64 heads of 64, state 128 in 8
    groups, chunks of 128, in bfloat16, forward and backward: the
    program keeps no ``[128, 128]`` square of any chunk for the backward
    and fits a chip many times over."""
    from ray_tpu.ops import ssm
    arg = _arg(v5e[0])

    def loss(x, dt, a, b, c, d):
        return ssm.mamba2_scan(x, dt, a, b, c, d, chunk=128).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        arg((1, 8192, 64, 64), jnp.bfloat16), arg((1, 8192, 64), jnp.float32),
        arg((64,), jnp.float32), arg((1, 8192, 8, 128), jnp.bfloat16),
        arg((1, 8192, 8, 128), jnp.bfloat16), arg((64,), jnp.float32)
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 4e9

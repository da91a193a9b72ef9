"""``ops/pallas/hc_maps.py``: the residual maps' kernel pair, interpreted
here, against ``ops/hyper_connections.py``'s ``jax.numpy`` function (which
``test_hyper_connections.py`` holds to the equations written out): values
and the gradients to the state, ``phi``, ``b`` and ``alpha``; which
programs ``hc_maps_path`` gives the kernels; and what a recomputed block
keeps of them."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import equations

from ray_tpu.ops import hyper_connections as hc
from ray_tpu.ops.pallas import hc_maps as kernels
from ray_tpu.ops.remat import MAPS_KEEPS, ROUTER_KEEPS
from ray_tpu.parallel import make_mesh

D = 32
ITERS, EPS = 20, 1e-6


def _inputs(n, batch, t, seed=0):
    rng = np.random.default_rng(seed)
    w = hc.map_width(n)
    operands = (jnp.asarray(rng.normal(size=(batch, t, n * D)), jnp.float32),
                jnp.asarray(rng.normal(size=(n * D, w)) * 0.3, jnp.float32),
                jnp.asarray(rng.normal(size=(w,)), jnp.float32),
                jnp.asarray([0.9, -1.3, 1.7], jnp.float32))
    cotangents = tuple(jnp.asarray(rng.normal(size=s), jnp.float32) for s in (
        (n, batch, t), (n, batch, t), (n, n, batch, t)))
    return operands, cotangents


def _maps_and_grads(maps, operands, cotangents):
    """The three maps and the gradients of their product with
    ``cotangents``, one jitted program."""
    def weighed(*a):
        got = maps(*a)
        return sum((g * c).sum() for g, c in zip(got, cotangents)), got

    (_, got), grads = jax.jit(jax.value_and_grad(
        weighed, argnums=(0, 1, 2, 3), has_aux=True))(*operands)
    return got, grads


# two token-block counts each: [batch, T] -> rows of 128 tokens -> blocks
# of 8 rows; the second of each n has a last block the rows do not fill
@pytest.mark.parametrize("n, batch, t, clamp", [
    (4, 1, 1024, 30.0), (4, 3, 768, 30.0), (4, 2, 1024, 0.5),
    (2, 1, 2048, 30.0), (2, 1, 1152, 30.0), (2, 2, 1024, 0.5)],
    ids=["n4_one_block", "n4_three_blocks_ragged", "n4_clamp_bites",
         "n2_two_blocks", "n2_two_blocks_ragged", "n2_clamp_bites"])
def test_the_kernels_are_the_jax_numpy_function(n, batch, t, clamp):
    """Every entry of every map and of the four gradients, float32
    against float32: the same operations in the same order, so what is
    left is the order of a sum."""
    operands, cotangents = _inputs(n, batch, t)
    static = dict(n=n, iters=ITERS, eps=EPS, clamp=clamp)
    want, want_grads = _maps_and_grads(
        functools.partial(hc._hc_maps_xla, norm_eps=1e-6, **static),
        operands, cotangents)
    got, grads = _maps_and_grads(
        functools.partial(kernels.hc_maps, interpret=True, **static),
        operands, cotangents)
    for name, g, w in zip(("h_pre", "h_post", "h_res"), got, want):
        assert g.shape == w.shape and g.dtype == jnp.float32, name
        np.testing.assert_allclose(g, w, atol=2e-6, err_msg=name)
    for name, g, w in zip(("dx", "dphi", "db", "dalpha"), grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(
            g, w, atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)
    if clamp < 1:       # it bit: a share of A's entries sit on it
        m0 = hc._hc_maps_xla(*operands, norm_eps=1e-6,
                             **{**static, "iters": 0})[2]
        on = jnp.isclose(jnp.abs(jnp.log(m0)), clamp, atol=1e-6)
        assert 0.2 < float(on.mean()) < 0.95


def test_a_bfloat16_state_gets_the_functions_gradients():
    """The cell's types: a bfloat16 state, float32 parameters. The maps
    are the function's to float32's last digits (one product, the same
    operands), and so are the parameters' gradients (the product's
    transposes are autodiff's own); the state's gradient, a sum of two
    bfloat16 terms, to a bfloat16 digit."""
    (x, *rest), cotangents = _inputs(4, 1, 1024)
    operands = (x.astype(jnp.bfloat16), *rest)
    static = dict(n=4, iters=ITERS, eps=EPS, clamp=30.0)
    want, want_grads = _maps_and_grads(
        functools.partial(hc._hc_maps_xla, norm_eps=1e-6, **static),
        operands, cotangents)
    got, grads = _maps_and_grads(
        functools.partial(kernels.hc_maps, interpret=True, **static),
        operands, cotangents)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-6)
    assert grads[0].dtype == jnp.bfloat16
    for name, g, w, rel in zip(("dx", "dphi", "db", "dalpha"), grads,
                               want_grads, (1e-2, 1e-5, 1e-5, 1e-5)):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        np.testing.assert_allclose(
            g, w, atol=rel * float(jnp.abs(w).max()), err_msg=name)


def test_a_sharded_batch_runs_the_kernels_a_device():
    """Under a ``shard_map`` over ``dp``: the same maps, and the
    parameters' gradients summed over the devices."""
    mesh = make_mesh({"dp": 2})
    operands, cotangents = _inputs(2, 2, 1024)
    static = dict(n=2, iters=3, eps=EPS, clamp=30.0)
    want, want_grads = _maps_and_grads(
        functools.partial(kernels.hc_maps, interpret=True, **static),
        operands, cotangents)
    got, grads = _maps_and_grads(
        functools.partial(kernels.hc_maps, interpret=True, mesh=mesh,
                          batch_axes=("dp",), **static),
        operands, cotangents)
    for g, w in zip((*got, *grads), (*want, *want_grads)):
        np.testing.assert_allclose(
            g, w, atol=1e-5 * max(float(jnp.abs(w).max()), 1.0))


@pytest.mark.parametrize("backend, shape, mesh_axes, path", [
    ("tpu", (1, 4096, 14336), None, "pallas"),
    ("cpu", (1, 4096, 14336), None, "xla"),
    ("tpu", (1, 4000, 14336), None, "xla"),      # T no whole tile
    ("tpu", (4096, 14336), None, "xla"),
    ("tpu", (8, 4096, 14336), {"dp": 2, "fsdp": 2}, "pallas"),
    ("tpu", (2, 4096, 14336), {"dp": 4}, "xla"),  # init tracing's batch
    ("tpu", (8, 4096, 14336), {"dp": 2, "sp": 2}, "xla"),
    ("tpu", (8, 4096, 14336), {"dp": 2, "tp": 2}, "xla")],
    ids=["tpu", "off_tpu", "ragged_tokens", "no_batch",
         "batch_mesh", "batch_the_mesh_does_not_divide", "sp", "tp"])
def test_which_programs_get_the_kernels(monkeypatch, backend, shape,
                                        mesh_axes, path):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    mesh = make_mesh(mesh_axes) if mesh_axes else None
    assert hc.hc_maps_path(shape, 4, mesh) == path


def test_without_a_mesh_a_program_of_several_devices_takes_xla(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.device_count() > 1       # conftest's eight
    assert hc.hc_maps_path((1, 4096, 14336), 4) == "xla"


# -- a recomputed block ------------------------------------------------------

@pytest.fixture
def on_the_kernels(monkeypatch):
    """``hc_maps`` on the kernels, interpreted: ``hc_maps_path`` is told
    what a TPU would answer."""
    monkeypatch.setattr(hc, "hc_maps_path", lambda *a, **kw: "pallas")
    monkeypatch.setattr(kernels, "hc_maps", functools.partial(
        kernels.hc_maps, interpret=True))


def _block_and_operands(hc_mult, remat):
    from ray_tpu.models.joyai import Block, JoyAIConfig, _keeps
    from ray_tpu.models.llama import rope_freqs
    from ray_tpu.ops import remat as keeping
    cfg = JoyAIConfig.tiny_xing(
        hc_mult=hc_mult, seq_len=128, hc_sinkhorn_iters=3, remat=remat,
        dtype=jnp.float32)
    block = keeping.block(Block, cfg.remat, _keeps(cfg))(
        cfg, False, name="h")
    x = jax.random.normal(jax.random.key(0),
                          (1, cfg.seq_len, hc_mult * cfg.n_embd))
    angles = rope_freqs(cfg.rope_dim, cfg.seq_len, cfg.rope_theta)
    params = block.init(jax.random.key(1), x, angles)

    def loss(params, x):
        return jnp.square(block.apply(params, x, angles)).sum()

    return cfg, loss, params, x


def _kernels_run(traced) -> list[str]:
    """The maps' ``pallas_call``s a traced function is left with once
    what nothing reads is taken out, by kernel, sorted."""
    from jax.interpreters import partial_eval as pe
    live, _ = pe.dce_jaxpr(traced.jaxpr, [True] * len(traced.jaxpr.outvars))
    return sorted(e.params["jaxpr"].debug_info.func_name
                  for e in equations(live) if e.primitive.name == "pallas_call")


@pytest.mark.parametrize("remat, forwards", [(True, 1), (False, 1)],
                         ids=["recomputed", "kept_whole"])
def test_a_recomputed_block_runs_each_forward_kernel_once(
        on_the_kernels, remat, forwards):
    """In the gradient's jaxpr of one block at ``hc_mult`` 4: one
    forward and one backward kernel a sub-layer, recomputed or not (the
    policy keeps the kernel's five named results, so the block's second
    pass makes none of them again)."""
    _, loss, params, x = _block_and_operands(4, remat)
    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
    assert _kernels_run(traced) == (
        ["_bwd_kernel"] * 2 + ["_fwd_kernel"] * 2 * forwards)


def test_a_policy_without_the_maps_names_runs_the_forward_kernel_twice(
        on_the_kernels, monkeypatch):
    """What the names are for: the parent's policy (the attention
    core's two names alone) makes every map again in the second pass."""
    from ray_tpu.models import joyai
    monkeypatch.setattr(joyai, "_keeps", lambda cfg: ())
    _, loss, params, x = _block_and_operands(4, True)
    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
    assert _kernels_run(traced) == ["_bwd_kernel"] * 2 + ["_fwd_kernel"] * 4


def test_recomputed_blocks_on_the_kernels_give_the_xla_paths_numbers(
        on_the_kernels, monkeypatch):
    """A recomputed block on the kernels against the same block on the
    ``jax.numpy`` maps, kept whole: the output's sum of squares and every
    gradient leaf."""
    _, loss, params, x = _block_and_operands(4, True)
    got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    monkeypatch.undo()
    _, loss, _, _ = _block_and_operands(4, False)
    want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(
            g, w, atol=1e-4 * max(float(jnp.abs(w).max()), 1e-3))


@pytest.mark.parametrize("hc_mult, keeps", [
    (4, (*ROUTER_KEEPS, *MAPS_KEEPS, "mixer_out_proj", "mlp_down",
         "moe_routed_out", "mlp_gate", "mlp_up", "attn_out", "attn_lse")),
    (1, (*ROUTER_KEEPS, "attn_out", "attn_lse"))],
    ids=["four_streams", "one_stream"])
def test_what_a_recomputed_block_keeps_by_name(monkeypatch, hc_mult, keeps):
    """The policy's names at ``hc_mult`` 4 and at ``hc_mult`` 1: the
    routers' first (since PR 68 a recomputed block runs neither its
    router's product nor the choice again), then where there are streams
    the maps' and each sub-layer's last products (``post``'s backward
    reads a sub-layer's output), then the cores' two."""
    from ray_tpu.models import joyai
    from ray_tpu.ops import remat
    asked, policy = [], remat.remat_policy
    monkeypatch.setattr(
        remat, "remat_policy",
        lambda *more: asked.append(more) or policy(*more))
    cfg = joyai.JoyAIConfig.tiny_xing(hc_mult=hc_mult, remat=True)
    remat.block(joyai.Block, cfg.remat, joyai._keeps(cfg))
    assert asked == [keeps[:-2]]
    assert remat.remat_keeps(*joyai._keeps(cfg)) == keeps
    assert remat.keeps_note(True, joyai._keeps(cfg)) == ",".join(keeps)

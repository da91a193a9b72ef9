"""The routers' choice as a kernel pair (``ops/pallas/router_choice.py``)
against XLA's lines (``ops/moe.py::_route`` / ``_route_sigmoid`` on the
``xla`` path: ``lax.top_k``, ``take_along_axis``, ``.at[].add``) on the
same float32 product; the kernels interpreted, at tiny token counts."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import live_kernel_calls, primitives

from ray_tpu.ops import moe
from ray_tpu.ops.pallas import router_choice
from ray_tpu.ops.remat import ROUTER_KEEPS, remat_policy

T, D = 128, 32
# (experts, top_k): OLMoE's and SmallThinker's, Nemotron's, Laguna's and
# JoyAI's, Qwen3-Next's
SHAPES = [(64, 8), (128, 6), (256, 8), (512, 10)]
ROUTERS = {
    "softmax": functools.partial(moe._route, norm_topk_prob=False),
    "softmax_norm": functools.partial(moe._route, norm_topk_prob=True),
    "sigmoid_scaled": functools.partial(
        moe._route_sigmoid, norm_topk_prob=True, route_scale=2.5)}


def _inputs(e, k, seed=0):
    """(x [T, D], router_w [D, E], select_bias [E], a cotangent for the
    weights [T, k], one for the probabilities' sum [E]). Experts 3 and 5
    score alike on every token (equal columns of ``router_w``, equal
    biases), and the bias is wide enough to move choices."""
    rng = np.random.default_rng(seed + e)
    x = rng.normal(size=(T, D))
    x[:, 0] = 1.0
    w = rng.normal(size=(D, e)) * 0.3
    w[0, 3] = 6.0           # the pair is chosen on most tokens
    w[:, 5] = w[:, 3]
    bias = rng.normal(size=(e,)) * 0.2
    bias[5] = bias[3] = 0.3
    return (jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32),
            jnp.asarray(bias, jnp.float32),
            jnp.asarray(rng.normal(size=(T, k)), jnp.float32),
            jnp.asarray(rng.normal(size=(e,)), jnp.float32))


def _layer(router, k, path, x, w, bias, cw, ce):
    """A router's every result, and a loss that reads the three that
    carry a gradient: -> (loss, (weights, experts, counts, the
    probabilities' sum, the z sum))."""
    args = (x, w, bias) if "sigmoid" in router else (x, w)
    weights, experts, prob_sum, z_sum, counts = ROUTERS[router](
        *args, top_k=k, path=path)
    if counts is None:      # ``_routed_ffn_local``'s line
        counts = jnp.zeros((w.shape[-1],), jnp.int32).at[
            experts.reshape(-1)].add(1)
    loss = jnp.sum(weights * cw) + jnp.sum(prob_sum * ce) + 0.3 * z_sum
    return loss, (weights, experts, counts, prob_sum, z_sum)


@functools.cache
def _both(router, e, k):
    """[the ``xla`` path's (loss, results, gradients), the kernels']."""
    inputs = _inputs(e, k)
    return [jax.jit(jax.value_and_grad(
        functools.partial(_layer, router, k, path), argnums=(0, 1, 2),
        has_aux=True))(*inputs) for path in ("xla", "interpret")]


CASES = [(router, e, k) for router in ROUTERS for e, k in SHAPES] + [
    ("softmax_norm", 72, 4)]     # experts that fill no 128 x 128 tile


@pytest.mark.parametrize("router, e, k", CASES)
def test_the_kernels_choose_what_top_k_chooses(router, e, k):
    """``experts`` equal exactly, the planted ties among them (the lowest
    index first), and so the routes each expert received."""
    ((_, want), _), ((_, got), _) = _both(router, e, k)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
    assert got[1].dtype == jnp.int32 and got[2].dtype == jnp.int32
    both = np.isin(want[1], (3, 5)).sum(axis=-1) == 2
    assert both.any()           # the equal pair is chosen together, 3 first
    at = np.asarray(want[1])[both]
    assert (np.argmax(at == 3, axis=-1) + 1 == np.argmax(at == 5, axis=-1)
            ).all()


@pytest.mark.parametrize("router, e, k", CASES)
def test_the_kernels_results_and_gradients_are_xlas(router, e, k):
    """The chosen weights, the two sums and the gradients with respect
    to ``x`` and ``router_w`` to float32 rounding; none to the bias."""
    ((want_loss, want), want_grads), ((got_loss, got), got_grads) = _both(
        router, e, k)
    for a, b in zip((got[0], got[3], got[4], got_loss, *got_grads[:2]),
                    (want[0], want[3], want[4], want_loss, *want_grads[:2])):
        np.testing.assert_allclose(
            a, b, rtol=2e-5, atol=2e-6 * float(np.abs(b).max()))
    assert float(jnp.abs(want_grads[0]).max()) > 0
    assert not np.asarray(got_grads[2]).any()
    assert not np.asarray(want_grads[2]).any()


def test_the_bias_moves_the_choice_and_not_the_weights():
    """With a bias that lifts the last experts over every score the
    choice is those experts, and the weights are still their scores."""
    e, k = 64, 8
    x, w, _, _, _ = _inputs(e, k)
    bias = jnp.where(jnp.arange(e) >= e - k, 2.0, 0.0)
    weights, experts, *_ = moe._route_sigmoid(
        x, w, bias, k, False, 1.0, "interpret")
    assert set(np.asarray(experts).reshape(-1)) == set(range(e - k, e))
    scores = jax.nn.sigmoid(moe._logits(x, w))
    np.testing.assert_allclose(
        weights, jnp.take_along_axis(scores, experts, axis=-1), rtol=1e-6)
    plain = moe._route_sigmoid(x, w, jnp.zeros((e,)), k, False, 1.0,
                               "interpret")[1]
    assert (np.asarray(plain) != np.asarray(experts)).any()


class _Routed(nn.Module):
    """A routed layer a block: what ``nn.remat`` wraps in the models."""
    router: str
    how: str

    @nn.compact
    def __call__(self, x):
        e, k, f = 64, 8, 16
        init = nn.initializers.normal(0.3)
        route = (functools.partial(moe._route_sigmoid, top_k=k,
                                   norm_topk_prob=True, route_scale=2.5)
                 if self.router == "sigmoid" else
                 functools.partial(moe._route, top_k=k, norm_topk_prob=True))
        args = (self.param("router", init, (D, e)),)
        if self.router == "sigmoid":
            args += (jnp.zeros((e,), jnp.float32),)
        y, aux, z, _ = moe._routed_ffn_local(
            x, args, self.param("gate", init, (e, D, f)),
            self.param("up", init, (e, D, f)),
            self.param("down", init, (e, f, D)), route=route,
            num_experts=e, top_k=k, router_path=self.how)
        return y, aux + z


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
@pytest.mark.parametrize("path", ["interpret", "xla"])
def test_a_recomputed_block_that_keeps_the_names_chooses_once(router, path):
    """Under ``nn.remat`` with ``remat_policy(*ROUTER_KEEPS)`` the
    gradient's program holds the forward kernel once and the backward
    kernel once, and no ``top_k``: the recomputed pass makes neither a
    second time, in the softmax router too. On XLA's lines the softmax
    router's ``top_k`` runs again (its values reach no name), the
    sigmoid's does not."""
    block = nn.remat(_Routed, policy=remat_policy(*ROUTER_KEEPS))(
        router, path)
    x = _inputs(64, 8)[0]
    params = block.init(jax.random.key(0), x)

    def loss(params):
        y, extra = block.apply(params, x)
        return jnp.sum(y * y) + extra

    traced = jax.make_jaxpr(jax.grad(loss))(params)
    if path == "interpret":
        outs = 5 if router == "softmax" else 3
        assert live_kernel_calls(traced) == [1, outs]
        assert primitives(traced, "top_k") == 0
    else:
        assert live_kernel_calls(traced) == []
        assert primitives(traced, "top_k") == (
            2 if router == "softmax" else 1)


def test_router_path_is_chosen_from_the_backend_the_shapes_and_the_mesh(
        monkeypatch):
    path = router_choice.router_path
    assert path(16384, 512, 10) == "xla"                # here: the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert path(16384, 512, 10) == "pallas"
    assert path(16384, 64, 8) == path(8192, 128, 6) == "pallas"
    assert path(100, 64, 8) == "xla"            # tokens the lanes cut
    assert path(128, 60, 8) == "xla"            # experts the sublanes cut
    assert path(128, 8, 9) == "xla"             # more routes than experts
    # one global program over several devices stays on XLA's lines; a
    # mesh that shards the tokens runs the pair a shard at a time
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.util import tracing
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    x, w = jnp.zeros((4, 256, D)), jnp.zeros((D, 64))
    one = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    dp4 = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    for mesh, b, want in ((None, 4, "pallas"), (one, 4, "pallas"),
                          (dp4, 4, "pallas"), (dp4, 3, "xla")):
        traced = jax.jit(functools.partial(
            moe.route_softmax, top_k=8, mesh=mesh)).trace(x[:b], w)
        assert notes.pop("moe_router_path") == want, (mesh, b)
        assert primitives(traced, "top_k") == (want == "xla")
        assert primitives(traced, "shard_map") == (mesh is dp4 and b == 4)
    assert moe._token_shards(dp4, x).tokens == 256


def test_the_tile_is_the_largest_that_divides_the_tokens():
    tile = router_choice._tile
    assert tile(16384, 512) == 256 and tile(16384, 64) == 2048
    assert tile(8192, 128) == 1024 and tile(384, 64) == 128
    assert tile(128 * 3 * 4, 64) == 512

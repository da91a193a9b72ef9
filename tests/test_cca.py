"""``ops/cca.py``: the pieces between CCA's projections and its kernel,
each against something that shares no code with it (``lax``'s own
convolution, ``models/llama.py``'s rotation, a sum written out), their
causality, and the backward pass that runs them again."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import apply_rope_half, rope_freqs
from ray_tpu.ops import cca
from ray_tpu.ops.attention import causal_attention

B, T, H, G, D = 2, 24, 4, 2, 16
C = (H + G) * D


def _rand(key, *shape, scale=1.0):
    return scale * jax.random.normal(jax.random.key(key), shape, jnp.float32)


def _operands(taps=(2, 2)):
    return dict(
        qk=_rand(0, B, T, C), v=_rand(1, B, T, G * D),
        conv0=(_rand(2, taps[0], C, scale=0.5), _rand(3, C, scale=0.1)),
        conv1=(_rand(4, taps[1], H + G, D, D, scale=0.2),
               _rand(5, C, scale=0.1)),
        tau=1.0 + _rand(6, G, scale=0.1),
        angles=rope_freqs(D // 2, T, 10000.0))


def test_shift_rows_moves_every_row_down_and_zeroes_the_first():
    x = _rand(0, B, T, 5)
    np.testing.assert_array_equal(cca.shift_rows(x)[:, 1:], x[:, :-1])
    np.testing.assert_array_equal(cca.shift_rows(x)[:, 0], 0)
    np.testing.assert_array_equal(cca.shift_rows(x, 3)[:, 3:], x[:, :-3])
    np.testing.assert_array_equal(cca.shift_rows(x, 3)[:, :3], 0)
    assert cca.shift_rows(x, 0) is x


@pytest.mark.parametrize("taps", [2, 3, 4])
def test_depthwise_conv_is_laxs_with_left_padding(taps):
    x, w, b = _rand(0, B, T, C), _rand(1, taps, C), _rand(2, C)
    want = jax.lax.conv_general_dilated(
        x, w[:, None, :], (1,), [(taps - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=C,
        precision="highest") + b
    np.testing.assert_allclose(cca.depthwise_causal_conv(x, w, b), want,
                               atol=1e-5)


@pytest.mark.parametrize("taps", [2, 3])
def test_grouped_conv_is_laxs_with_a_group_a_head(taps):
    x = _rand(0, B, T, C)
    w, b = _rand(1, taps, H + G, D, D, scale=0.3), _rand(2, C)
    # lax wants [taps, in a group, all outputs], a group's outputs together
    kernel = jnp.moveaxis(w, 1, 2).reshape(taps, D, C)
    want = jax.lax.conv_general_dilated(
        x, kernel, (1,), [(taps - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=H + G,
        precision="highest") + b
    with jax.default_matmul_precision("highest"):
        got = cca.grouped_causal_conv(x, w, b)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_qk_mean_written_out():
    q, k = _rand(0, B, T, G, H // G, D), _rand(1, B, T, G, D)
    m_q, m_k = cca.qk_mean(q, k)
    for g in range(G):
        for r in range(H // G):
            np.testing.assert_allclose(m_q[:, :, g, r],
                                       (q[:, :, g, r] + k[:, :, g]) / 2,
                                       rtol=1e-6)
        np.testing.assert_allclose(
            m_k[:, :, g], (sum(q[:, :, g, r] for r in range(H // G))
                           / (H // G) + k[:, :, g]) / 2, rtol=1e-6)


def test_l2_normalise_gives_every_head_the_length_sqrt_d():
    x = _rand(0, B, T, H, D, scale=3.0)
    y = cca.l2_normalise(x)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1), np.sqrt(D),
                               rtol=1e-5)
    np.testing.assert_allclose(y / jnp.linalg.norm(y, axis=-1, keepdims=True),
                               x / jnp.linalg.norm(x, axis=-1, keepdims=True),
                               atol=1e-6)


def test_partial_rope_rotates_the_first_lanes_in_halves_and_no_other():
    x = _rand(0, B, T, H, D)
    angles = rope_freqs(D // 2, T, 10000.0)
    y = cca.partial_rope(x, angles, jnp.float32)
    np.testing.assert_array_equal(y[..., D // 2:], x[..., D // 2:])
    np.testing.assert_allclose(y[..., :D // 2],
                               apply_rope_half(x[..., :D // 2], angles),
                               atol=1e-6)
    np.testing.assert_array_equal(y[:, 0], x[:, 0])     # position 0: no turn
    assert cca.partial_rope(x, angles, jnp.bfloat16).dtype == jnp.bfloat16


def _o(ops):
    return cca.cca_attention(
        ops["qk"], ops["v"], ops["conv0"], ops["conv1"], ops["tau"],
        ops["angles"], n_head=H, n_kv_head=G, attn_fn=causal_attention)


@pytest.mark.parametrize("taps", [(2, 2), (3, 4)])
def test_a_change_at_row_t_moves_no_output_before_t(taps):
    """Both convolutions, the mixing and the attention are causal."""
    ops = _operands(taps)
    at = 11
    moved = {**ops, "qk": ops["qk"].at[:, at].add(1.0),
             "v": ops["v"].at[:, at].add(1.0)}
    a, b = _o(ops), _o(moved)
    np.testing.assert_array_equal(a[:, :at], b[:, :at])
    assert float(jnp.abs(a[:, at:] - b[:, at:]).max()) > 1e-3
    for conv, args in ((cca.depthwise_causal_conv, ops["conv0"]),
                       (cca.grouped_causal_conv, ops["conv1"])):
        a, b = conv(ops["qk"], *args), conv(moved["qk"], *args)
        np.testing.assert_array_equal(a[:, :at], b[:, :at])
        # a kernel of K taps reaches K - 1 rows on
        reach = args[0].shape[0] - 1
        assert float(jnp.abs(a[:, at + reach] - b[:, at + reach]).max()) > 0
        np.testing.assert_array_equal(a[:, at + reach + 1:],
                                      b[:, at + reach + 1:])


def test_a_query_head_reads_its_own_groups_key_and_value():
    """Head i attends to key/value head i // (H / G): a change to group
    1's value moves heads 2 and 3 alone."""
    ops = _operands()
    moved = {**ops, "v": ops["v"].at[..., D:].add(1.0)}
    a = _o(ops).reshape(B, T, H, D)
    b = _o(moved).reshape(B, T, H, D)
    np.testing.assert_array_equal(a[:, :, :2], b[:, :, :2])
    np.testing.assert_allclose(b[:, :, 2:] - a[:, :, 2:], 1.0, atol=1e-5)


def test_the_backward_pass_that_runs_the_passes_again_is_the_gradient():
    """``mixed_qk`` keeps its inputs alone and differentiates
    ``_qk_for_kernel`` a second time: the same cotangents as autodiff
    straight through it."""
    ops = _operands()
    names = ("qk", "conv0", "conv1", "tau")

    def loss(fn):
        def f(qk, conv0, conv1, tau):
            q, k = fn(qk, conv0, conv1, tau, ops["angles"], H, G)
            return (q * q[:, ::-1]).sum() + (k * jnp.cos(k)).sum()
        return jax.grad(f, argnums=(0, 1, 2, 3))(*(ops[n] for n in names))

    got, want = loss(cca.mixed_qk), loss(cca._qk_for_kernel)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    text = jax.jit(lambda *a: loss(cca.mixed_qk)).lower().as_text()
    assert "optimization_barrier" in text


def test_the_temperature_scales_the_keys_group_by_group():
    ops = _operands()
    q, k = cca.mixed_qk(ops["qk"], ops["conv0"], ops["conv1"], ops["tau"],
                        ops["angles"], H, G)
    np.testing.assert_allclose(jnp.linalg.norm(q, axis=-1), np.sqrt(D),
                               rtol=1e-5)
    np.testing.assert_allclose(
        jnp.linalg.norm(k, axis=-1),
        jnp.broadcast_to(np.sqrt(D) * ops["tau"], (B, T, G)), rtol=1e-5)


def test_cca_path_names_what_runs_the_passes():
    assert cca.cca_path() == "xla"

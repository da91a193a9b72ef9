"""``ops/ssm.py``: the chunked Mamba-2 scan against the recurrence as
written (a ``lax.scan`` over time, here), values and gradients, at
sequence lengths that are and are not a multiple of the chunk; the
convolution and the gated norm against their formulas; and what the
backward keeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm

B, H, P, G, N, CHUNK = 2, 4, 8, 2, 16, 16


def _recurrence(x, dt, A, Bm, C, D):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D x_t."""
    b, _, h, p = x.shape
    rep = h // Bm.shape[2]
    Bh, Ch = (jnp.repeat(z, rep, axis=2) for z in (Bm, C))

    def step(S, at):
        x_t, dt_t, b_t, c_t = at
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t) + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, Bm.shape[-1])),
                        tuple(jnp.moveaxis(z, 1, 0)
                              for z in (x, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1)


def _inputs(T, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (B, T, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, T, H))),
            -jnp.exp(jax.random.uniform(ks[2], (H,), maxval=2.7)),
            jax.random.normal(ks[3], (B, T, G, N)),
            jax.random.normal(ks[4], (B, T, G, N)),
            jax.random.normal(ks[5], (H,)))


@pytest.mark.parametrize("T", [64, 16, 50, 7],
                         ids=["four_chunks", "one_chunk", "ragged_tail",
                              "under_a_chunk"])
def test_chunked_scan_is_the_recurrence(T):
    args = _inputs(T, seed=T)
    with jax.default_matmul_precision("highest"):
        got = ssm.mamba2_scan(*args, chunk=CHUNK)
        want = _recurrence(*args)
    assert got.shape == want.shape == (B, T, H, P)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("T", [48, 41], ids=["whole_chunks", "ragged_tail"])
def test_chunked_scan_gradients_are_the_recurrences(T):
    """Every input's gradient, through the checkpoint that recomputes
    inside the chunks from the boundary states."""
    args = _inputs(T, seed=T)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(
            ssm.mamba2_scan(*a, chunk=CHUNK) ** 2), range(6))(*args)
        want = jax.grad(lambda *a: jnp.sum(_recurrence(*a) ** 2),
                        range(6))(*args)
    for name, g, w in zip("x dt A B C D".split(), got, want):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()),
            err_msg=name)


def test_scan_in_bfloat16_keeps_decays_in_float32():
    """bf16 operands, float32 accumulation and decays: within bf16's
    rounding of the float32 result, and bf16 out."""
    x, dt, A, Bm, C, D = _inputs(64)
    want = _recurrence(x, dt, A, Bm, C, D)
    got = ssm.mamba2_scan(x.astype(jnp.bfloat16), dt, A,
                          Bm.astype(jnp.bfloat16), C.astype(jnp.bfloat16),
                          D, chunk=CHUNK)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want).max()
    assert float(err) < 0.03 * float(jnp.abs(want).max())


def test_the_backward_keeps_boundary_states_and_no_chunk_squares():
    """What the checkpoint saves for the backward: the inputs and the
    states entering each chunk, nothing of ``[chunk, chunk]``."""
    from jax._src.ad_checkpoint import saved_residuals
    T = 64
    args = _inputs(T)
    saved = saved_residuals(
        lambda *a: ssm.mamba2_scan(*a, chunk=CHUNK).sum(), *args)
    shapes = [tuple(aval.shape) for aval, _ in saved]
    assert (B, T // CHUNK, G, H // G, P, N) in shapes     # the boundaries
    assert not [s for s in shapes if s[-2:] == (CHUNK, CHUNK)]
    whole = sum(int(np.prod(s)) for s in shapes)
    given = sum(a.size for a in args)
    assert whole <= given + B * (T // CHUNK) * H * P * N + T * B * H


def test_conv_is_four_shifted_multiply_adds_then_silu():
    ks = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(ks[0], (2, 9, 6))
    w = jax.random.normal(ks[1], (4, 6))
    b = jax.random.normal(ks[2], (6,))
    want = np.zeros((2, 9, 6), np.float32)
    for t in range(9):
        acc = np.asarray(b).copy()
        for j in range(4):
            if t - 3 + j >= 0:
                acc = acc + np.asarray(w[j]) * np.asarray(x[:, t - 3 + j])
        want[:, t] = acc / (1 + np.exp(-acc))
    np.testing.assert_allclose(ssm.causal_conv1d_silu(x, w, b), want,
                               rtol=1e-5, atol=1e-6)
    # causal: the future does not reach back
    later = x.at[:, 5:].set(0.0)
    np.testing.assert_array_equal(
        ssm.causal_conv1d_silu(later, w, b)[:, :5],
        ssm.causal_conv1d_silu(x, w, b)[:, :5])


def test_gated_norm_normalises_each_group():
    ks = jax.random.split(jax.random.key(1), 3)
    y = jax.random.normal(ks[0], (2, 5, 12))
    z = jax.random.normal(ks[1], (2, 5, 12))
    scale = jax.random.normal(ks[2], (12,))
    g = np.asarray(y) * np.asarray(z) / (1 + np.exp(-np.asarray(z)))
    g = g.reshape(2, 5, 3, 4)
    g = g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(
        ssm.gated_group_rms_norm(y, z, scale, 3, 1e-5),
        g.reshape(2, 5, 12) * np.asarray(scale), rtol=1e-5, atol=1e-6)
    grads = jax.grad(lambda *a: ssm.gated_group_rms_norm(
        *a, 3, 1e-5).sum(), (0, 1, 2))(y, z, scale)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)

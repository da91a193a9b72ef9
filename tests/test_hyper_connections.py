"""``ops/hyper_connections.py``: the residual path's maps and mixes
against the equations written out on one token at a time (numpy,
float64), with gates of order 1 so that every map depends on the state;
what 20 Sinkhorn normalisations leave of the row and column sums; the
clamp; the layout the docstring promises; and the meshes refused by
name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import hyper_connections as hc
from ray_tpu.ops.pallas import program
from ray_tpu.parallel import make_mesh

B, T, D = 2, 5, 8
ITERS, EPS, CLAMP = 20, 1e-6, 30.0


def _inputs(n, seed=0, alpha=(0.9, -1.3, 1.7)):
    ks = jax.random.split(jax.random.key(seed), 4)
    w = hc.map_width(n)
    return (jax.random.normal(ks[0], (B, T, n * D)),
            jax.random.normal(ks[1], (n * D, w)) * 0.4,
            jax.random.normal(ks[2], (w,)),
            jnp.asarray(alpha),
            jax.random.normal(ks[3], (B, T, D)))


def _written_out(x, phi, b, alpha, y, n, iters=ITERS):
    """The module docstring's equations, a token at a time, float64:
    (H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n], u [B, T, D],
    X' [B, T, n D])."""
    x, phi, b, alpha, y = (np.asarray(z, np.float64)
                           for z in (x, phi, b, alpha, y))
    pre, post, res = (np.zeros((B, T, n)), np.zeros((B, T, n)),
                      np.zeros((B, T, n, n)))
    u, new = np.zeros((B, T, D)), np.zeros((B, T, n * D))
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))    # noqa: E731
    for i in range(B):
        for t in range(T):
            v = x[i, t]
            m = (v / np.sqrt((v * v).mean() + 1e-6)) @ phi
            pre[i, t] = sig(alpha[0] * m[:n] + b[:n])
            post[i, t] = 2.0 * sig(alpha[1] * m[n:2 * n] + b[n:2 * n])
            a = np.clip(alpha[2] * m[2 * n:] + b[2 * n:], -CLAMP, CLAMP)
            mat = np.exp(a.reshape(n, n))
            for _ in range(iters):
                mat = mat / (mat.sum(0, keepdims=True) + EPS)   # columns
                mat = mat / (mat.sum(1, keepdims=True) + EPS)   # rows
            res[i, t] = mat
            xs = v.reshape(n, D)
            u[i, t] = pre[i, t] @ xs
            new[i, t] = (mat @ xs + post[i, t][:, None] * y[i, t]).ravel()
    return pre, post, res, u, new


@pytest.mark.parametrize("n", [2, 4])
def test_the_op_is_the_written_out_equations(n):
    """Maps, ``pre`` and ``post`` with gates of order 1: every entry of
    every map against the one-token-at-a-time float64 computation; the
    maps come with the tokens in the lanes."""
    x, phi, b, alpha, y = _inputs(n)
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = hc.hc_maps(x, phi, b, alpha, n=n, iters=ITERS,
                                          eps=EPS, clamp=CLAMP)
        u = hc.hc_pre(x, h_pre)
        new = hc.hc_post(x, y, h_post, h_res)
    assert h_pre.shape == h_post.shape == (n, B, T)
    assert h_res.shape == (n, n, B, T) and new.shape == x.shape
    pre, post, res, want_u, want_new = _written_out(x, phi, b, alpha, y, n)
    np.testing.assert_allclose(jnp.moveaxis(h_pre, 0, -1), pre, atol=2e-6)
    np.testing.assert_allclose(jnp.moveaxis(h_post, 0, -1), post, atol=4e-6)
    np.testing.assert_allclose(jnp.moveaxis(h_res, (0, 1), (-2, -1)), res,
                               atol=2e-6)
    np.testing.assert_allclose(u, want_u, atol=1e-5)
    np.testing.assert_allclose(new, want_new, atol=1e-5)
    # the maps differ by token and by entry: nothing here is a constant
    assert float(jnp.std(h_pre)) > 0.05 and float(jnp.std(h_res)) > 0.05
    if n > 2:       # a doubly stochastic 2 x 2 matrix is symmetric
        assert float(jnp.abs(h_res - jnp.swapaxes(h_res, 0, 1)).max()) > 0.05


@pytest.mark.parametrize("n", [2, 4])
def test_gradients_are_the_written_out_equations(n):
    """Every input's gradient of a scalar of ``u`` and ``X'`` against
    the same equations in ``jax.numpy`` on a [B, T, n, n] array (the
    layout this op avoids), through all 20 normalisations."""
    x, phi, b, alpha, y = _inputs(n, seed=1)

    def op(x, phi, b, alpha, y):
        maps = hc.hc_maps(x, phi, b, alpha, n=n, iters=ITERS, eps=EPS,
                          clamp=CLAMP)
        return (jnp.sum(hc.hc_pre(x, maps[0]) ** 2)
                + jnp.sum(jnp.sin(hc.hc_post(x, y, maps[1], maps[2]))))

    def plain(x, phi, b, alpha, y):
        m = (x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)) @ phi
        pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + b[:n])
        post = 2 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + b[n:2 * n])
        mat = jnp.exp(jnp.clip(alpha[2] * m[..., 2 * n:] + b[2 * n:],
                               -CLAMP, CLAMP)).reshape(B, T, n, n)
        for _ in range(ITERS):
            mat = mat / (mat.sum(-2, keepdims=True) + EPS)
            mat = mat / (mat.sum(-1, keepdims=True) + EPS)
        xs = x.reshape(B, T, n, D)
        u = jnp.einsum("bti,btid->btd", pre, xs)
        new = (jnp.einsum("btij,btjd->btid", mat, xs)
               + post[..., None] * y[:, :, None])
        return jnp.sum(u ** 2) + jnp.sum(jnp.sin(new))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(op, range(5))(x, phi, b, alpha, y)
        want = jax.grad(plain, range(5))(x, phi, b, alpha, y)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()))
        assert float(jnp.abs(w).max()) > 1e-3


def test_rows_and_columns_sum_to_one_within_what_20_iterations_leave():
    """The rows' sums are off by ``eps`` (theirs is the last
    normalisation), the columns' by what the iteration has not closed
    yet: with logits as the model initialises them (gates 0.01, ``b``
    normal(1.0)) under 1e-4 after 20 and worse after 2; with gates of
    order 1 the logits spread over tens and 20 iterations leave the
    columns percents off. ``res_row_err`` is the rows' number."""
    n = 4
    x, phi, b, _, _ = _inputs(n, seed=2)

    def maps(iters, gate):
        return hc.hc_maps(x, phi, b, jnp.full((3,), gate), n=n, iters=iters,
                          eps=EPS, clamp=CLAMP)[2]

    def off(h_res, axis):
        return float(jnp.abs(h_res.sum(axis) - 1.0).max())
    h_res = maps(ITERS, 0.01)
    assert off(h_res, 1) < 5e-6 and off(h_res, 0) < 1e-4
    assert float(hc.res_row_err(h_res)) == pytest.approx(off(h_res, 1))
    assert off(maps(2, 0.01), 0) > 10 * off(h_res, 0)
    assert float(h_res.min()) > 0.0
    strong = maps(ITERS, 1.7)
    assert off(strong, 1) < 5e-6 and 1e-3 < off(strong, 0) < 0.2


def test_the_clamp_bites_at_30():
    """Logits of +-1000 enter ``exp`` as +-30: the result is finite and
    is what logits of exactly +-30 give; unclamped they would be inf /
    inf."""
    n = 2
    x = jnp.ones((1, 1, n * D))
    phi = jnp.zeros((n * D, hc.map_width(n)))
    sign = jnp.asarray([1.0, -1.0, -1.0, 1.0])

    def res(size, clamp):
        b = jnp.concatenate([jnp.zeros(2 * n), size * sign])
        return hc.hc_maps(x, phi, b, jnp.ones(3), n=n, iters=ITERS, eps=EPS,
                          clamp=clamp)[2][..., 0, 0]
    got = res(1000.0, CLAMP)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, res(30.0, CLAMP), rtol=1e-6)
    np.testing.assert_allclose(got, jnp.eye(n), atol=1e-6)
    assert not bool(jnp.isfinite(res(1000.0, 1e9)).all())


def test_streams_start_as_copies_and_end_as_a_sum():
    n = 4
    x = jax.random.normal(jax.random.key(0), (B, T, D))
    state = hc.hc_expand(x, n)
    assert state.shape == (B, T, n * D)
    for part in hc.streams(state, n):
        np.testing.assert_array_equal(part, x)
    np.testing.assert_allclose(hc.hc_collapse(state, n), n * x, rtol=1e-6)
    assert float(hc.stream_spread(state, n)) == 0.0
    other = state.at[..., :D].multiply(3.0)
    want = np.asarray(other, np.float64).reshape(B, T, n, D)
    off = want - want.mean(2, keepdims=True)
    assert float(hc.stream_spread(other, n)) == pytest.approx(
        np.sqrt((off ** 2).sum() / (want ** 2).sum()), rel=1e-5)


def test_one_stream_with_unit_maps_is_the_plain_residual():
    x = jax.random.normal(jax.random.key(0), (B, T, D))
    y = jax.random.normal(jax.random.key(1), (B, T, D))
    one = jnp.ones((1, B, T))
    np.testing.assert_array_equal(hc.hc_pre(x, one), x)
    np.testing.assert_allclose(hc.hc_post(x, y, one, one[None]), x + y,
                               rtol=1e-6)


def test_the_state_stays_in_its_type_and_the_maps_in_float32():
    n = 4
    x, phi, b, alpha, y = _inputs(n)
    x, y = x.astype(jnp.bfloat16), y.astype(jnp.bfloat16)
    maps = hc.hc_maps(x, phi, b, alpha, n=n, iters=ITERS, eps=EPS,
                      clamp=CLAMP)
    assert all(m.dtype == jnp.float32 for m in maps)
    assert hc.hc_pre(x, maps[0]).dtype == jnp.bfloat16
    assert hc.hc_post(x, y, maps[1], maps[2]).dtype == jnp.bfloat16
    # no [.., n, d] array: the streams are lane slices of [.., n d]
    text = jax.jit(lambda x, y: hc.hc_post(x, y, maps[1], maps[2])).lower(
        x, y).as_text()
    assert f"x{n}x{D}x" not in text


@pytest.mark.parametrize("axis, says", [
    ("sp", "sequence split over chips"), ("tp", "lanes split over chips")])
def test_a_split_state_is_refused_by_name(axis, says):
    mesh = make_mesh({axis: 2}, devices=jax.devices()[:2])
    def refuse(mesh):   # as ``models/joyai.py`` does before a layer is built
        program.refuse(mesh, "hyper-connections", **hc.SPLIT_STATE)
    with pytest.raises(NotImplementedError, match=f"{axis}=2") as err:
        refuse(mesh)
    assert says in str(err.value)
    refuse(None)
    refuse(make_mesh({"dp": 2}, devices=jax.devices()[:2]))

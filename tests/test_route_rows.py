"""``ops/pallas/route_rows.py``: ``take_rows`` / ``sum_rows`` against
the gather and the float32 scatter-add they replaced in
``ops/moe.py::_slab`` (PR 43), on the plain path and on the product
path with ``tgmm`` in the interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.pallas import route_rows as rr

T = 256                     # tokens: two tiles of the one-hot
HELD = 4


def _slab(k, rows, lo, n_live, seed=0, every=(), none=()):
    """A sorted order over ``T * k`` routes of which ``n_live`` landed
    on ``HELD`` held experts, and its slab ``lo .. lo + rows``. Tokens
    in ``every`` have all their routes landed, in ``none`` not one."""
    rng = np.random.default_rng(seed)
    routes = T * k
    fixed = np.zeros(routes, bool)
    landed = np.zeros(routes, bool)
    for tok in every:
        landed[tok * k:(tok + 1) * k] = fixed[tok * k:(tok + 1) * k] = True
    for tok in none:
        fixed[tok * k:(tok + 1) * k] = True
    free = np.flatnonzero(~fixed)
    landed[rng.choice(free, n_live - int(landed.sum()), replace=False)] = True
    key = np.where(landed, rng.integers(0, HELD, routes), HELD)
    order = np.argsort(key, kind="stable").astype(np.int32)
    pos = np.empty(routes, np.int32)
    pos[order] = np.arange(routes, dtype=np.int32)
    order = np.pad(order, (0, -routes % rows))
    return rr.Slab(jnp.asarray(order[lo:lo + rows]),
                   jnp.asarray(pos.reshape(T, k)), jnp.int32(lo),
                   jnp.int32(n_live))


def _inputs(k, rows, d, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (T, d), dtype)
    ys = jax.random.normal(keys[1], (rows, d), dtype)
    w = jax.random.uniform(keys[2], (T, k), jnp.float32, 0.1, 1.0)
    dy = jax.random.normal(keys[3], (T, d), dtype)
    return x, ys, w, dy


def old_take(x, slab):
    """PR 41's dispatch: gather every row of the slab, mask."""
    live = (slab.sorted_index() >= 0)[:, None]
    return jnp.where(live, x[slab.route // slab.pos.shape[-1]],
                     jnp.zeros((), x.dtype))


def old_sum(ys, w, slab, exact=False):
    """PR 41's combine: mask, weigh, float32 scatter-add, cast; with
    ``exact`` the products in float32 too, as ``sum_rows`` makes them."""
    t, k = slab.pos.shape
    live = (slab.sorted_index() >= 0)[:, None]
    ws = w.astype(ys.dtype).reshape(-1)[slab.route][:, None]
    ys = jnp.where(live, ys, jnp.zeros((), ys.dtype))
    prod = (ys.astype(jnp.float32) * ws.astype(jnp.float32) if exact
            else (ys * ws).astype(jnp.float32))
    return jnp.zeros((t, ys.shape[-1]), jnp.float32).at[
        slab.route // k].add(prod).astype(ys.dtype)


def f32(a):
    return np.asarray(a.astype(jnp.float32))


PATHS = ("xla", "interpret")
# (rows, lo, n_live): nothing live, one row, a tile of the product's
# rows exactly, mid-tile, the whole slab, a second slab (lo > 0) partly
# and wholly live
LIVE = [(512, 0, 0), (512, 0, 1), (512, 0, 256), (512, 0, 301),
        (512, 0, 512), (512, 512, 700), (256, 256, 512)]


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("rows,lo,n_live", LIVE)
def test_take_rows_is_the_gather_with_zeros_past_the_live_rows(
        path, rows, lo, n_live):
    slab = _slab(6, rows, lo, n_live)
    x, *_ = _inputs(6, rows, 256, jnp.bfloat16)
    got = rr.take_rows(x, slab, path)
    np.testing.assert_array_equal(f32(got), f32(old_take(x, slab)))
    live = int(np.clip(n_live - lo, 0, rows))
    assert not f32(got)[live:].any()


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("rows,lo,n_live", LIVE)
def test_sum_rows_is_the_scatter_add_and_reads_no_dead_row(
        path, rows, lo, n_live):
    slab = _slab(6, rows, lo, n_live)
    _, ys, w, _ = _inputs(6, rows, 256, jnp.float32)
    live = int(np.clip(n_live - lo, 0, rows))
    poisoned = ys.at[live:].set(jnp.nan)     # what a kernel may leave there
    got = rr.sum_rows(poisoned, w, slab, path)
    np.testing.assert_allclose(f32(got), f32(old_sum(ys, w, slab)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("k", [1, 6, 8])
@pytest.mark.parametrize("d", [256, 640, 1152])   # 2048, 2560, 2688 cut
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_sum_rows_at_each_top_k_width_and_dtype(path, k, d, dtype):
    rows = 512 if k > 1 else T
    slab = _slab(k, rows, 0, min(rows, T * k) * 3 // 5, seed=k,
                 every=(3, 200) if k > 1 else (), none=(0, 77, 255))
    _, ys, w, _ = _inputs(k, rows, d, dtype, seed=d)
    got = rr.sum_rows(ys, w, slab, path)
    assert got.dtype == dtype and got.shape == (T, d)
    assert not f32(got)[[0, 77, 255]].any()      # no route here: zeros
    if k == 1:      # one product a token, rounded once: the old path's bits
        np.testing.assert_array_equal(f32(got), f32(old_sum(ys, w, slab)))
    else:           # exact products summed in float32, cast once
        np.testing.assert_allclose(
            f32(got), f32(old_sum(ys, w, slab, exact=True)),
            rtol=2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5, atol=1e-6)
    wide = rr.sum_rows(ys, w, slab, path, jnp.float32)
    assert wide.dtype == jnp.float32
    np.testing.assert_allclose(
        f32(wide), f32(old_sum(ys.astype(jnp.float32),
                               w.astype(dtype).astype(jnp.float32), slab)),
        rtol=1e-5, atol=1e-5)


def _loss(take, add, dy):
    def f(x, w):
        xs = take(x)
        return jnp.sum(add(jnp.tanh(xs) * 2.0, w).astype(jnp.float32)
                       * dy.astype(jnp.float32))
    return f


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("k,rows,lo,n_live", [
    (1, 256, 0, 120), (6, 512, 0, 301), (8, 512, 0, 512), (6, 512, 512, 700)])
def test_both_gradients_of_each_are_the_scatter_forms(
        path, k, rows, lo, n_live):
    """``d take_rows / d src`` is a ``sum_rows``, ``d sum_rows / d src``
    a ``take_rows`` times the weights, ``d sum_rows / d w`` the row dots
    un-sorted: against ``jax.grad`` through XLA's gather and
    scatter-add, in float32."""
    slab = _slab(k, rows, lo, n_live, seed=7, every=(5,), none=(9,))
    x, _, w, dy = _inputs(k, rows, 256, jnp.float32, seed=1)
    want = jax.grad(_loss(lambda x: old_take(x, slab),
                          lambda ys, w: old_sum(ys, w, slab), dy),
                    (0, 1))(x, w)
    got = jax.grad(_loss(lambda x: rr.take_rows(x, slab, path),
                         lambda ys, w: rr.sum_rows(ys, w, slab, path), dy),
                   (0, 1))(x, w)
    for g, wnt in zip(got, want):
        np.testing.assert_allclose(f32(g), f32(wnt), rtol=1e-4, atol=1e-5)
    assert not f32(got[0])[9].any() and not f32(got[1])[9].any()


@pytest.mark.parametrize("path", PATHS)
def test_the_bfloat16_gradients_agree_between_the_paths(path):
    slab = _slab(6, 512, 0, 301, seed=3)
    x, _, w, dy = _inputs(6, 512, 256, jnp.bfloat16, seed=2)

    def grads(p):
        return jax.grad(_loss(lambda x: rr.take_rows(x, slab, p),
                              lambda ys, w: rr.sum_rows(ys, w, slab, p), dy),
                        (0, 1))(x, w)
    for g, wnt in zip(grads(path), grads("xla")):
        np.testing.assert_allclose(f32(g), f32(wnt), rtol=2.0 ** -6,
                                   atol=2.0 ** -6)


def test_rows_path_is_chosen_from_the_backend_and_the_shapes(monkeypatch):
    assert rr.rows_path(16384, 49152) == "xla"          # here: the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rr.rows_path(16384, 49152) == "tgmm"
    assert rr.rows_path(8192, 6144) == "tgmm"
    assert rr.rows_path(100, 512) == "xla"              # tokens the tile cuts
    assert rr.rows_path(256, 300) == "xla"              # rows the tile cuts
    assert rr.rows_path(128, 96) == "tgmm"              # a slab under a tile

"""``ops/ssm.py::mamba1_scan``: the chunked Mamba-1 selective scan against
the recurrence itself, token by token, values and every gradient; rows
that the chunk does and does not divide; the state across chunk
boundaries; the skip; the meshes it refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm
from ray_tpu.util import tracing


def _inputs(seed, t, c=24, n=4, b=2):
    ks = jax.random.split(jax.random.key(seed), 6)
    return {
        "x": jax.random.normal(ks[0], (b, t, c)),
        "dt": jax.nn.softplus(jax.random.normal(ks[1], (b, t, c)) - 1.0),
        "A": -jnp.exp(jax.random.normal(ks[2], (c, n))),
        "B": jax.random.normal(ks[3], (b, t, n)),
        "C": jax.random.normal(ks[4], (b, t, n)),
        "D": jax.random.normal(ks[5], (c,))}


def _per_token(x, dt, A, B, C, D):
    """The recurrence, one token at a time over a [b, C, N] state."""
    def token(h, row):
        x, dt, B, C = row
        h = (jnp.exp(dt[..., None] * A) * h
             + (dt * x)[..., None] * B[:, None, :])
        return h, jnp.sum(h * C[:, None, :], -1) + D * x
    rows = tuple(jnp.moveaxis(z, 1, 0) for z in (x, dt, B, C))
    _, y = jax.lax.scan(
        token, jnp.zeros((x.shape[0], x.shape[2], A.shape[1])), rows)
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("t, chunk", [(32, 8), (29, 8), (5, 8), (32, 32),
                                      (33, 1)],
                         ids=["whole_chunks", "a_tail", "under_one_chunk",
                              "one_chunk", "a_row_a_chunk"])
def test_values_and_every_gradient_are_the_recurrences(t, chunk):
    args = _inputs(t, t)
    weight = jax.random.normal(jax.random.key(99), args["x"].shape)

    def total(f):
        return lambda a: jnp.sum(f(**a) * weight)

    chunked = lambda **a: ssm.mamba1_scan(**a, chunk=chunk)  # noqa: E731
    got, want = chunked(**args), _per_token(**args)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    g_got = jax.grad(total(chunked))(args)
    g_want = jax.grad(total(_per_token))(args)
    for name in args:
        scale = float(jnp.abs(g_want[name]).max())
        np.testing.assert_allclose(g_got[name], g_want[name],
                                   atol=3e-5 * scale, err_msg=name)


def test_the_state_crosses_chunk_boundaries():
    """A token of the first chunk is read in the last: its input moves
    every later output, as far as the decay lets it."""
    args = _inputs(3, 32)
    # slow decays, so that the first row's write is still there at row 31
    args["A"] = args["A"] * 0.01
    moved = {**args, "x": args["x"].at[:, 0].add(1.0)}
    delta = (ssm.mamba1_scan(**moved, chunk=8)
             - ssm.mamba1_scan(**args, chunk=8))
    assert float(jnp.abs(delta[:, 31]).max()) > 1e-3
    np.testing.assert_allclose(
        delta, _per_token(**moved) - _per_token(**args), atol=2e-5)


def test_the_chunk_changes_nothing():
    args = _inputs(4, 40)
    want = ssm.mamba1_scan(**args, chunk=40)
    for chunk in (4, 8, 16, 64):
        np.testing.assert_allclose(ssm.mamba1_scan(**args, chunk=chunk), want,
                                   rtol=2e-5, atol=2e-5)


def test_the_skip_is_d_times_x():
    args = _inputs(5, 16)
    without = ssm.mamba1_scan(**{**args, "D": jnp.zeros_like(args["D"])},
                              chunk=8)
    np.testing.assert_allclose(
        ssm.mamba1_scan(**args, chunk=8) - without, args["D"] * args["x"],
        atol=1e-5)


def test_bfloat16_rows_are_cast_up_at_the_door():
    args = _inputs(6, 16)
    low = {k: (v.astype(jnp.bfloat16) if k in "xBC" else v)
           for k, v in args.items()}
    up = {k: v.astype(jnp.float32) for k, v in low.items()}
    got = ssm.mamba1_scan(**low, chunk=8)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, _per_token(**up), rtol=2e-5, atol=2e-5)


def test_the_path_is_named_and_sp_and_tp_are_refused(monkeypatch):
    from ray_tpu.parallel.mesh import make_mesh
    said = {}
    monkeypatch.setattr(tracing, "note_trace", said.update)
    args = _inputs(7, 16)
    ssm.mamba1_scan(**args, chunk=8)
    assert said == {"ssm_path": "xla_chunked", "ssm_chunk": 8}
    devices = jax.devices()[:2]
    assert ssm.mamba1_path((2, 16, 24), 8, make_mesh(
        {"dp": 2}, devices=devices)) == "xla_chunked"
    for axis in ("sp", "tp"):
        with pytest.raises(NotImplementedError, match=f"{axis}=2"):
            ssm.mamba1_scan(**args, chunk=8,
                            mesh=make_mesh({axis: 2}, devices=devices))
    with pytest.raises(ValueError, match="chunk"):
        ssm.mamba1_path((2, 16, 24), 0)

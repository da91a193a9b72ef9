"""``ops/mamba1.py::mamba1_scan``: the chunked Mamba-1 selective scan against
the recurrence itself, token by token, values and every gradient; rows
that the chunk does and does not divide; the state across chunk
boundaries; the skip; the meshes it refuses. Then the kernel pair of
``ops/pallas/mamba1_scan.py``, interpreted, against both; what its
bodies compute in; and which programs ``mamba1_path`` gives it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import mamba1
from ray_tpu.ops.pallas import mamba1_scan as kernels
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

    chunked = lambda **a: mamba1.mamba1_scan(**a, chunk=chunk)  # noqa: E731
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
    delta = (mamba1.mamba1_scan(**moved, chunk=8)
             - mamba1.mamba1_scan(**args, chunk=8))
    assert float(jnp.abs(delta[:, 31]).max()) > 1e-3
    np.testing.assert_allclose(
        delta, _per_token(**moved) - _per_token(**args), atol=2e-5)


def test_the_chunk_changes_nothing():
    args = _inputs(4, 40)
    want = mamba1.mamba1_scan(**args, chunk=40)
    for chunk in (4, 8, 16, 64):
        np.testing.assert_allclose(mamba1.mamba1_scan(**args, chunk=chunk), want,
                                   rtol=2e-5, atol=2e-5)


def test_the_skip_is_d_times_x():
    args = _inputs(5, 16)
    without = mamba1.mamba1_scan(**{**args, "D": jnp.zeros_like(args["D"])},
                              chunk=8)
    np.testing.assert_allclose(
        mamba1.mamba1_scan(**args, chunk=8) - without, args["D"] * args["x"],
        atol=1e-5)


def test_bfloat16_rows_are_cast_up_at_the_door():
    args = _inputs(6, 16)
    low = {k: (v.astype(jnp.bfloat16) if k in "xBC" else v)
           for k, v in args.items()}
    up = {k: v.astype(jnp.float32) for k, v in low.items()}
    got = mamba1.mamba1_scan(**low, chunk=8)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, _per_token(**up), rtol=2e-5, atol=2e-5)


def test_the_path_is_named_and_sp_and_tp_are_refused(monkeypatch):
    from ray_tpu.parallel.mesh import make_mesh
    said = {}
    monkeypatch.setattr(tracing, "note_trace", said.update)
    args = _inputs(7, 16)
    mamba1.mamba1_scan(**args, chunk=8)
    assert said == {"ssm_path": "xla_chunked", "ssm_chunk": 8}
    devices = jax.devices()[:2]
    assert mamba1.mamba1_path((2, 16, 24), 4, 8, make_mesh(
        {"dp": 2}, devices=devices)) == "xla_chunked"
    for axis in ("sp", "tp"):
        with pytest.raises(NotImplementedError, match=f"{axis}=2"):
            mamba1.mamba1_scan(**args, chunk=8,
                            mesh=make_mesh({axis: 2}, devices=devices))
    with pytest.raises(ValueError, match="chunk"):
        mamba1.mamba1_path((2, 16, 24), 4, 0)


# ---------------------------------------------------------------------------
# the kernel pair, interpreted
# ---------------------------------------------------------------------------

_ROWS = 8       # a row block of the interpreted kernels


def _kernel_inputs(seed, t, b=2):
    """The least the kernels tile: one vreg of channels, eight states."""
    return _inputs(seed, t, c=kernels.GROUP, n=8, b=b)


def _on_kernels(**a):
    return kernels.mamba1_scan(**a, rows=_ROWS, interpret=True)


def _on_xla(**a):
    return mamba1._mamba1_xla_chunked(**a, chunk=4)


@pytest.mark.parametrize("reference", [_on_xla, _per_token],
                         ids=["xla_chunked", "per_token"])
@pytest.mark.parametrize("t", [24, 21, 5],
                         ids=["whole_blocks", "a_ragged_tail",
                              "under_one_block"])
def test_the_kernels_values_and_six_cotangents(t, reference):
    args = _kernel_inputs(t, t)
    weight = jax.random.normal(jax.random.key(98), args["x"].shape)

    def total(f):
        return lambda a: jnp.sum(f(**a) * weight)

    got, want = _on_kernels(**args), reference(**args)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    g_got = jax.grad(total(_on_kernels))(args)
    g_want = jax.grad(total(reference))(args)
    assert set(g_got) == {"x", "dt", "A", "B", "C", "D"}
    for name in args:
        scale = float(jnp.abs(g_want[name]).max())
        np.testing.assert_allclose(g_got[name], g_want[name],
                                   atol=3e-5 * scale, err_msg=name)


def test_the_padded_tail_neither_decays_nor_writes():
    """Rows past ``T`` are padding inside the last block: the cotangents
    of ``A`` and ``D``, which sum over every row a block walks, are those
    of the rows that exist."""
    args = _kernel_inputs(8, 13)
    longer = {k: (jnp.pad(v, ((0, 0), (0, 3), (0, 0))) if v.ndim == 3 else v)
              for k, v in args.items()}
    weight = jax.random.normal(jax.random.key(97), longer["x"].shape)

    def total(a, rows):
        return jnp.sum(_on_kernels(**a) * weight[:, :rows])

    short, whole = (jax.grad(total)(a, a["x"].shape[1])
                    for a in (args, longer))
    for name in ("A", "D"):
        np.testing.assert_allclose(short[name], whole[name], rtol=1e-6,
                                   err_msg=name)


def test_the_kernels_state_crosses_one_block_boundary():
    """Two blocks: the first row's write is read in the last row of the
    second, and the last row's cotangent reaches the first row's input."""
    args = _kernel_inputs(3, 2 * _ROWS)
    args["A"] = args["A"] * 0.01
    moved = {**args, "x": args["x"].at[:, 0].add(1.0)}
    delta = _on_kernels(**moved) - _on_kernels(**args)
    assert float(jnp.abs(delta[:, -1]).max()) > 1e-3
    np.testing.assert_allclose(
        delta, _per_token(**moved) - _per_token(**args), atol=2e-5)
    last = lambda f: lambda a: jnp.sum(f(**a)[:, -1])  # noqa: E731
    got = jax.grad(last(_on_kernels))(args)["x"][:, 0]
    assert float(jnp.abs(got).max()) > 1e-3
    np.testing.assert_allclose(
        got, jax.grad(last(_per_token))(args)["x"][:, 0], atol=2e-5)


def test_the_kernels_take_bfloat16_rows_and_give_their_dtypes_back():
    args = _kernel_inputs(6, 16)
    low = {k: (v.astype(jnp.bfloat16) if k in "xBC" else v)
           for k, v in args.items()}
    up = {k: v.astype(jnp.float32) for k, v in low.items()}
    got = _on_kernels(**low)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, _on_xla(**up), rtol=2e-5, atol=2e-5)
    grads = jax.grad(lambda a: jnp.sum(_on_kernels(**a)))(low)
    want = jax.grad(lambda a: jnp.sum(_on_xla(**a)))(up)
    for name, g in grads.items():
        assert g.dtype == low[name].dtype and g.shape == low[name].shape
        scale = float(jnp.abs(want[name]).max())
        np.testing.assert_allclose(
            g.astype(jnp.float32), want[name],
            atol=(2 ** -7 if g.dtype == jnp.bfloat16 else 3e-5) * scale,
            err_msg=name)


def _kernel_bodies(which):
    """The equations of one kernel's body, loops and branches opened."""
    a = _kernel_inputs(0, 16, b=1)
    f32 = jnp.float32
    groups = a["x"].shape[-1] // kernels.GROUP
    scalars = lambda z: z.astype(f32).reshape(2, 1, _ROWS * 8)  # noqa: E731
    operands = (scalars(a["B"]), scalars(a["C"]),
                a["x"].astype(jnp.bfloat16), a["dt"],
                a["A"].T.reshape(8, groups, 8, 128).swapaxes(0, 1),
                a["D"].reshape(groups, 8, 128))
    static = dict(rows=_ROWS, unroll=2, interpret=True)
    if which == "forward":
        outer = jax.make_jaxpr(
            functools.partial(kernels._mamba1_fwd, **static))(*operands)
    else:
        entering = jnp.zeros((1, 2, groups, 8, 8, 128), f32)
        outer = jax.make_jaxpr(functools.partial(
            kernels._mamba1_bwd, **static))(*operands, entering, a["dt"])

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if inside:
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, inside
                                or eqn.primitive.name == "pallas_call")

    eqns = list(walk(outer.jaxpr, False))
    assert eqns, "no pallas_call found"
    return eqns


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_every_exp_and_product_in_a_kernel_is_float32(which):
    eqns = _kernel_bodies(which)
    names = {e.primitive.name for e in eqns}
    assert {"exp", "mul", "add"} <= names
    low = []        # what is not float32: x's tiles as they are read
    for e in eqns:
        if e.primitive.name == "dot_general":
            assert e.params["precision"] == jax.lax.Precision.HIGHEST, e
        floats = [v.aval.dtype for v in (*e.invars, *e.outvars)
                  if hasattr(v.aval, "dtype")
                  and jnp.issubdtype(v.aval.dtype, jnp.floating)]
        if (any(d != jnp.float32 for d in floats)
                and not list(jax.core.jaxprs_in_params(e.params))):
            low.append(e.primitive.name)    # a loop holds the refs it reads
    # a block's rows of x, eight tiles of every eight rows of a group,
    # each read and cast up once; nothing else is not float32
    assert sorted(low) == ["convert_element_type"] * 8 + ["get"] * 8


_CELL = (1, 4096, 5120)


@pytest.mark.parametrize(
    "backend, devices, shape, states, mesh_axes, want",
    [("tpu", 1, _CELL, 16, None, "pallas_chunked"),
     ("tpu", 1, (2, 100, 2048), 8, None, "pallas_chunked"),
     ("cpu", 1, _CELL, 16, None, "xla_chunked"),
     ("tpu", 1, _CELL, 4, None, "xla_chunked"),
     ("tpu", 1, (1, 4096, 100), 16, None, "xla_chunked"),
     ("tpu", 1, (1, 4096, 640), 16, None, "xla_chunked"),
     ("tpu", 2, _CELL, 16, None, "xla_chunked"),
     ("tpu", 2, (2, 4096, 5120), 16, {"dp": 2}, "xla_chunked"),
     ("tpu", 1, _CELL, 16, {"dp": 1}, "pallas_chunked"),
     ("tpu", 2, _CELL, 16, {"sp": 2}, NotImplementedError),
     ("tpu", 2, _CELL, 16, {"tp": 2}, NotImplementedError)],
    ids=["the_cell", "whole_vregs", "cpu", "four_states", "100_channels",
         "five_lane_tiles", "two_devices_no_mesh", "dp2", "a_mesh_of_one",
         "sp2", "tp2"])
def test_which_programs_get_the_kernels(monkeypatch, backend, devices, shape,
                                        states, mesh_axes, want):
    from ray_tpu.parallel.mesh import make_mesh
    mesh = mesh_axes and make_mesh(mesh_axes, devices=jax.devices()[:devices])
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    if isinstance(want, str):
        assert mamba1.mamba1_path(shape, states, 4, mesh) == want
    else:
        with pytest.raises(want):
            mamba1.mamba1_path(shape, states, 4, mesh)


def test_the_scan_hands_a_tpu_program_to_the_kernels(monkeypatch):
    """``mamba1_scan`` where the path says ``pallas_chunked``: the kernels'
    entry gets the arguments as they came, and the note says the rows of
    a block of theirs, not the XLA path's chunk."""
    said = {}
    monkeypatch.setattr(tracing, "note_trace", said.update)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(kernels, "mamba1_scan", functools.partial(
        kernels.mamba1_scan, interpret=True))
    args = _kernel_inputs(9, 70, b=1)
    got = mamba1.mamba1_scan(**args, chunk=4)
    assert said == {"ssm_path": "pallas_chunked", "ssm_chunk": kernels.ROWS}
    np.testing.assert_allclose(got, _on_xla(**args), rtol=2e-5, atol=2e-5)

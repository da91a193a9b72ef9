"""``ops/ssm.py``: the chunked Mamba-2 scan against the recurrence as
written (a ``lax.scan`` over time, here), values and gradients, at
sequence lengths that are and are not a multiple of the chunk; the
convolution and the gated norm against their formulas; what the
backward keeps; and the same for the scan's Pallas kernels
(``ops/pallas/ssd_scan.py``) in ``interpret`` mode, with which of the
two paths ``scan_path`` picks for a shape, a backend and a mesh; and the
gated norm's kernels (``ops/pallas/gated_norm.py``) the same way, with
``norm_path``, and that file's second pair, Kimi Delta Attention's
output gate; and the convolution's (``ops/pallas/causal_conv.py``),
with ``conv_path``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import conv1d, gated_norm as norms, ssm
from ray_tpu.ops.pallas import causal_conv, gated_norm, ssd_scan
from ray_tpu.ops.remat import SSD_SCAN_OUT, SSD_SCAN_STATES

B, H, P, G, N, CHUNK = 2, 4, 8, 2, 16, 16


def _grad(f, argnums=0):
    """``jax.grad``, compiled as one program: run operation by operation,
    every step of an interpreted kernel is a compile of its own."""
    return jax.jit(jax.grad(f, argnums))


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


def _inputs(T, seed=0, shape=(B, H, P, G, N)):
    b, h, p, g, n = shape
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (b, T, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, T, h))),
            -jnp.exp(jax.random.uniform(ks[2], (h,), maxval=2.7)),
            jax.random.normal(ks[3], (b, T, g, n)),
            jax.random.normal(ks[4], (b, T, g, n)),
            jax.random.normal(ks[5], (h,)))


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
        got = _grad(lambda *a: jnp.sum(
            ssm.mamba2_scan(*a, chunk=CHUNK) ** 2), range(6))(*args)
        want = _grad(lambda *a: jnp.sum(_recurrence(*a) ** 2),
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
    np.testing.assert_allclose(conv1d.causal_conv1d_silu(x, w, b), want,
                               rtol=1e-5, atol=1e-6)
    # causal: the future does not reach back
    later = x.at[:, 5:].set(0.0)
    np.testing.assert_array_equal(
        conv1d.causal_conv1d_silu(later, w, b)[:, :5],
        conv1d.causal_conv1d_silu(x, w, b)[:, :5])


def test_gated_norm_normalises_each_group():
    ks = jax.random.split(jax.random.key(1), 3)
    y = jax.random.normal(ks[0], (2, 5, 12))
    z = jax.random.normal(ks[1], (2, 5, 12))
    scale = jax.random.normal(ks[2], (12,))
    g = np.asarray(y) * np.asarray(z) / (1 + np.exp(-np.asarray(z)))
    g = g.reshape(2, 5, 3, 4)
    g = g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(
        norms.gated_group_rms_norm(y, z, scale, 3, 1e-5),
        g.reshape(2, 5, 12) * np.asarray(scale), rtol=1e-5, atol=1e-6)
    grads = _grad(lambda *a: norms.gated_group_rms_norm(
        *a, 3, 1e-5).sum(), (0, 1, 2))(y, z, scale)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


# ---------------------------------------------------------------------------
# the Pallas kernels, interpreted here: chunks of 128, a state of 128,
# heads in blocks of eight
# ---------------------------------------------------------------------------

# (batch, heads, head width, groups, state), T
KERNEL_CASES = {
    "whole_chunks_batch_two": ((2, 8, 16, 1, 128), 256),
    "ragged_tail_two_groups": ((1, 16, 16, 2, 128), 300),
    "two_head_blocks_a_group": ((1, 16, 16, 1, 128), 256),
}


def _kernel(*args):
    return ssd_scan.ssd_scan(*args, chunk=128, interpret=True)


def _close_gradients(got, want):
    """Within 1e-4 of each element and of the gradient's largest: the
    running sums of a chunk's 128 log-decays, and their cotangents',
    are float32 sums in another order than the recurrence's."""
    for name, g, w in zip("x dt A B C D".split(), got, want):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-4 * float(jnp.abs(w).max()),
            err_msg=name)


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_kernel_scan_is_the_recurrence(case):
    shape, T = KERNEL_CASES[case]
    args = _inputs(T, seed=T, shape=shape)
    got = _kernel(*args)
    want = _recurrence(*args)
    assert got.shape == want.shape == args[0].shape
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_kernel_scan_gradients_are_the_recurrences(case):
    """All six, through the backward kernel that recomputes a chunk's
    squares from the inputs and the state entering it."""
    shape, T = KERNEL_CASES[case]
    args = _inputs(T, seed=T + 1, shape=shape)
    got = _grad(lambda *a: jnp.sum(_kernel(*a) ** 2), range(6))(*args)
    want = _grad(lambda *a: jnp.sum(_recurrence(*a) ** 2),
                    range(6))(*args)
    _close_gradients(got, want)


def test_kernel_scan_is_the_xla_scan_to_float32_rounding():
    """The two paths of ``mamba2_scan`` at one tileable shape: values
    and gradients."""
    args = _inputs(256, seed=3, shape=(1, 8, 16, 1, 128))

    def xla(*a):
        return ssm._padded_scan(*a, chunk=128)

    with jax.default_matmul_precision("highest"):
        want = xla(*args)
        np.testing.assert_allclose(
            _kernel(*args), want, rtol=1e-5,
            atol=1e-5 * float(jnp.abs(want).max()))
        got = _grad(lambda *a: jnp.sum(_kernel(*a) ** 2),
                       range(6))(*args)
        want = _grad(lambda *a: jnp.sum(xla(*a) ** 2), range(6))(*args)
    _close_gradients(got, want)


def test_kernel_scan_in_bfloat16_keeps_decays_in_float32():
    shape = (1, 8, 16, 1, 128)
    x, dt, A, Bm, C, D = _inputs(256, shape=shape)
    want = _recurrence(x, dt, A, Bm, C, D)
    low = (x.astype(jnp.bfloat16), dt, A, Bm.astype(jnp.bfloat16),
           C.astype(jnp.bfloat16), D)
    got = _kernel(*low)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want).max()
    assert float(err) < 0.03 * float(jnp.abs(want).max())
    grads = _grad(lambda *a: jnp.sum(
        _kernel(*a).astype(jnp.float32) ** 2), range(6))(*low)
    wants = _grad(lambda *a: jnp.sum(_recurrence(*a) ** 2),
                     range(6))(x, dt, A, Bm, C, D)
    assert [g.dtype for g in grads] == [a.dtype for a in low]
    for name, g, w in zip("x dt A B C D".split(), grads, wants):
        err = jnp.linalg.norm(g.astype(jnp.float32) - w)
        assert float(err) < 0.03 * float(jnp.linalg.norm(w)), name


def test_kernel_scan_takes_the_difference_of_the_sums_not_the_product():
    """``A`` = -16 at ``dt`` = 0.1 runs the sum to -204.8 over a chunk:
    ``exp`` of its negation alone is past float32 (3e38 at 88.7), the
    decay between two steps is not."""
    shape = (1, 8, 16, 1, 128)
    x, _, _, Bm, C, D = _inputs(256, seed=5, shape=shape)
    dt = jnp.full((1, 256, 8), 0.1)
    A = jnp.full((8,), -16.0)
    args = (x, dt, A, Bm, C, D)
    got = _kernel(*args)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, _recurrence(*args), rtol=1e-4,
                               atol=1e-4)
    grads = _grad(lambda *a: jnp.sum(_kernel(*a) ** 2), range(6))(*args)
    wants = _grad(lambda *a: jnp.sum(_recurrence(*a) ** 2),
                     range(6))(*args)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    _close_gradients(grads, wants)


def test_kernel_scan_at_the_cells_head_width_in_bfloat16():
    """Heads of 64 (a block is 512 lanes, as in the Nemotron cell) with
    bfloat16 operands, against the XLA scan given the same operands:
    both round a chunk's weights and states to bfloat16 at the same
    places, so they differ by the order of float32 sums alone; and
    against the recurrence in float32 within bfloat16's rounding."""
    shape = (1, 8, 64, 1, 128)
    x, dt, A, Bm, C, D = _inputs(256, seed=7, shape=shape)
    low = (x.astype(jnp.bfloat16), dt, A, Bm.astype(jnp.bfloat16),
           C.astype(jnp.bfloat16), D)

    def xla(*a):
        return ssm._padded_scan(*a, chunk=128)

    def loss(f):
        return lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2)

    got, want = _kernel(*low), xla(*low)
    assert got.dtype == want.dtype == jnp.bfloat16
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    # a bfloat16 result: one step of its rounding apart at most
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < 2 ** -7 * scale
    exact = _recurrence(x, dt, A, Bm, C, D)
    assert float(jnp.abs(got.astype(jnp.float32) - exact).max()) < (
        0.03 * float(jnp.abs(exact).max()))
    grads = _grad(loss(_kernel), range(6))(*low)
    wants = _grad(loss(xla), range(6))(*low)
    exacts = _grad(loss(_recurrence), range(6))(x, dt, A, Bm, C, D)
    for name, g, w, e in zip("x dt A B C D".split(), grads, wants, exacts):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.linalg.norm(g - w)) < (
            0.01 * float(jnp.linalg.norm(w))), name
        assert float(jnp.linalg.norm(g - e)) < (
            0.03 * float(jnp.linalg.norm(e))), name


CELL = ((1, 8192, 64, 64), (1, 8192, 8, 128), 128)


# (batch, heads, head width, groups, state), T: chunks of 256, and one
# group over two or three head blocks (granite-4.0-h-micro's scan: a
# group's square is made in each of its head blocks, and ``dB``, ``dC``
# leave the backward as a float32 share a head block, summed outside)
KERNEL_CASES_256 = {
    "one_group_two_head_blocks": ((1, 16, 16, 1, 128), 512),
    "one_group_three_head_blocks_ragged_tail": ((1, 24, 16, 1, 128), 300),
    "two_groups_batch_two": ((2, 16, 16, 2, 128), 256),
}


@pytest.mark.parametrize("case", KERNEL_CASES_256)
def test_kernel_scan_at_chunk_256_is_the_xla_scan(case):
    """``y`` and the cotangents of ``x``, ``dt``, ``A``, ``B``, ``C``,
    ``D`` against ``_ssd``'s at the same chunk, and ``y`` against the
    recurrence as written."""
    shape, T = KERNEL_CASES_256[case]
    args = _inputs(T, seed=T + 2, shape=shape)

    def kernel(*a):
        return ssd_scan.ssd_scan(*a, chunk=256, interpret=True)

    def xla(*a):
        return ssm._padded_scan(*a, chunk=256)

    with jax.default_matmul_precision("highest"):
        got, want = jax.jit(kernel)(*args), xla(*args)
        top = float(jnp.abs(want).max())
        # a chunk's 256 log-decays are summed in float32 in another
        # order on each of the three paths
        for other in (want, _recurrence(*args)):
            np.testing.assert_allclose(got, other, rtol=1e-4,
                                       atol=1e-4 * top)
        grads = _grad(lambda *a: jnp.sum(kernel(*a) ** 2), range(6))(*args)
        wants = _grad(lambda *a: jnp.sum(xla(*a) ** 2), range(6))(*args)
    _close_gradients(grads, wants)


def test_one_groups_partial_db_dc_come_back_in_the_operands_dtype():
    """bfloat16 operands, one group over two head blocks: the shares of
    ``dB`` and ``dC`` are float32 inside and their sum is cast once."""
    x, dt, A, Bm, C, D = _inputs(256, seed=9, shape=(1, 16, 16, 1, 128))
    low = (x.astype(jnp.bfloat16), dt, A, Bm.astype(jnp.bfloat16),
           C.astype(jnp.bfloat16), D)

    def loss(f):
        return lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2)

    grads = _grad(loss(lambda *a: ssd_scan.ssd_scan(
        *a, chunk=256, interpret=True)), range(6))(*low)
    wants = _grad(loss(lambda *a: ssm._padded_scan(*a, chunk=256)),
                  range(6))(*low)
    assert [g.dtype for g in grads] == [a.dtype for a in low]
    for name, g, w in zip("x dt A B C D".split(), grads, wants):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.linalg.norm(g - w)) < (
            0.01 * float(jnp.linalg.norm(w))), name


@pytest.mark.parametrize("policy, forwards", [
    ((SSD_SCAN_OUT, SSD_SCAN_STATES), 1), ((SSD_SCAN_OUT,), 2), ((), 2)],
    ids=["both_names", "the_output_alone", "no_name"])
def test_a_policy_that_keeps_the_scans_two_names_runs_its_forward_once(
        policy, forwards):
    """The gradient of a recomputed function round the kernels, with
    what nothing reads taken out as lowering takes it out: the forward
    kernel (2 results) once where the policy keeps ``y`` and the
    entering states, twice where it loses either; the backward (6)
    once. Outside a policy the names change nothing."""
    from conftest import live_kernel_calls
    args = _inputs(256, seed=4, shape=(1, 16, 16, 1, 128))

    def kernel(*a):
        return jnp.sin(ssd_scan.ssd_scan(*a, chunk=256, interpret=True))

    kept = jax.checkpoint(
        kernel, policy=jax.checkpoint_policies.save_only_these_names(*policy))
    traced = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kept(*a) ** 2), range(6)))(*args)
    assert live_kernel_calls(traced) == [2] * forwards + [6]
    assert live_kernel_calls(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kernel(*a) ** 2), range(6)))(*args)) == [2, 6]


@pytest.mark.parametrize("h, g, path, squares", [
    (64, 8, "pallas_chunked", 1), (64, 1, "pallas_chunked", 8),
    (64, 1, "chunked_xla", 1)],
    ids=["nemotrons_groups", "granites_one_group", "the_xla_path"])
def test_score_squares_per_group_counts_a_groups_head_blocks(h, g, path,
                                                             squares):
    assert ssm.score_squares_per_group(h, g, path) == squares
    assert ssd_scan.shapes_ok(h, 64, g, 128, 256)


def _mesh(**axes):
    from ray_tpu.parallel.mesh import make_mesh
    size = int(np.prod(list(axes.values())))
    return make_mesh(axes, devices=jax.devices()[:size])


@pytest.mark.parametrize("backend, x, state, chunk, path", [
    ("tpu", *CELL, "pallas_chunked"),
    ("cpu", *CELL, "chunked_xla"),
    ("tpu", CELL[0], (1, 8192, 8, 16), 128, "chunked_xla"),
    ("tpu", *CELL[:2], 16, "chunked_xla"),
    ("tpu", (1, 8192, 64, 8), CELL[1], 128, "chunked_xla"),
    ("tpu", (1, 8192, 32, 64), CELL[1], 128, "chunked_xla"),
], ids=["the_cell_on_a_tpu", "the_cell_on_a_cpu", "a_state_of_16",
        "a_chunk_of_16", "eight_heads_fill_no_tile",
        "four_heads_a_group"])
def test_scan_path_reads_the_backend_and_the_shapes(
        monkeypatch, backend, x, state, chunk, path):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert ssm.scan_path(x, state, chunk) == path


@pytest.mark.parametrize("axes, batch, path", [
    (None, 1, "chunked_xla"),
    ({"dp": 1}, 1, "pallas_chunked"),
    ({"dp": 4}, 4, "pallas_chunked"),
    ({"dp": 2, "fsdp": 2}, 8, "pallas_chunked"),
    ({"dp": 4}, 2, "chunked_xla"),
    ({"dp": 2, "tp": 2}, 4, "chunked_xla"),
    ({"ep": 2}, 4, "chunked_xla"),
], ids=["no_mesh_in_a_process_of_eight_devices", "a_mesh_of_one_device",
        "dp", "dp_and_fsdp", "a_batch_dp_does_not_divide", "dp_and_tp",
        "ep_alone"])
def test_scan_path_reads_the_devices_the_program_spans(
        monkeypatch, axes, batch, path):
    """A ``pallas_call`` has no SPMD rule: the kernels serve a program
    of one device, or under a ``shard_map`` one whose mesh shards the
    batch and nothing else. This process has eight (virtual) devices,
    so with no mesh given the count says the program may span them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.device_count() > 1
    mesh = None if axes is None else _mesh(**axes)
    x, state = ((batch, *shape[1:]) for shape in CELL[:2])
    assert ssm.scan_path(x, state, 128, mesh) == path


def test_kernel_scan_over_a_batch_sharded_mesh_is_the_one_device_scan():
    """Under the ``shard_map`` over ``dp`` each device scans its own
    sequences; ``A`` and ``D`` are whole on both and their gradients
    are the sum of the two devices'."""
    mesh = _mesh(dp=2)
    args = _inputs(256, seed=11, shape=(2, 8, 16, 1, 128))

    def sharded(*a):
        return ssd_scan.ssd_scan(*a, chunk=128, interpret=True, mesh=mesh,
                                 batch_axes=("dp",))

    def loss(f):
        return lambda *a: jnp.sum(f(*a) ** 2)

    want = _kernel(*args)
    got = jax.jit(sharded)(*args)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert got.sharding.spec[0] in ("dp", ("dp",))
    grads = jax.jit(jax.grad(loss(sharded), range(6)))(*args)
    wants = _grad(loss(_kernel), range(6))(*args)
    for name, g, w in zip("x dt A B C D".split(), grads, wants):
        np.testing.assert_allclose(
            g, w, rtol=1e-5, atol=1e-5 * float(jnp.abs(w).max()),
            err_msg=name)


def test_shapes_the_kernels_do_not_tile_are_refused_by_name():
    with pytest.raises(ValueError, match="do not tile"):
        ssd_scan.ssd_scan(*_inputs(64), chunk=16, interpret=True)


# ---------------------------------------------------------------------------
# the gated norm's kernels, interpreted here, against the XLA function
# (which this backend's ``gated_group_rms_norm`` is)
# ---------------------------------------------------------------------------

def _norm_inputs(shape, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    y, z, dout = (jax.random.normal(k, shape).astype(dtype) for k in ks[:3])
    return y, z, 1.0 + 0.5 * jax.random.normal(ks[3], shape[-1:]), dout


def _norm_kernel(groups, **kw):
    return lambda *a: gated_norm.gated_norm(*a, groups=groups, eps=1e-5,
                                            interpret=True, **kw)


@functools.partial(jax.jit, static_argnums=0)
def _value_and_grads(f, y, z, scale, dout):
    out, vjp = jax.vjp(f, y, z, scale)
    return (out, *vjp(dout))


# (batch, T, C), groups: row blocks of 128 rows
NORM_CASES = {
    "one_group_a_ragged_last_block": ((2, 300, 256), 1),
    "eight_groups_a_ragged_last_block": ((2, 300, 1024), 8),
    "eight_groups_whole_blocks": ((2, 256, 1024), 8),
    "fewer_rows_than_a_strip_holds": ((2, 7, 256), 2),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", NORM_CASES)
def test_kernel_norm_is_the_xla_norm(monkeypatch, case, dtype):
    """The value and all three gradients. Both are float32 inside and
    round once on the way out: in float32 they differ by the order of
    the sums, in bfloat16 by one step of the result's rounding at
    most; ``scale``'s gradient is a float32 sum either way."""
    shape, groups = NORM_CASES[case]
    monkeypatch.setattr(gated_norm, "_BLOCK_BYTES",
                        128 * shape[-1] * jnp.dtype(dtype).itemsize)
    args = _norm_inputs(shape, dtype, seed=shape[1])
    got = _value_and_grads(_norm_kernel(groups), *args)
    want = _value_and_grads(
        lambda *a: norms.gated_group_rms_norm(*a, groups, 1e-5), *args)
    step = 1e-5 if dtype == jnp.float32 else 2 ** -7
    for name, g, w in zip("out dy dz dscale".split(), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        tol = 1e-5 if name == "dscale" else step
        np.testing.assert_allclose(
            g, w, rtol=tol, atol=tol * float(jnp.abs(w).max()),
            err_msg=name)


NORM_CELL = (1, 8192, 4096)


@pytest.mark.parametrize("backend, shape, groups, path", [
    ("tpu", NORM_CELL, 8, "pallas"),
    ("cpu", NORM_CELL, 8, "xla"),
    ("tpu", NORM_CELL, 1, "pallas"),
    ("tpu", (1, 8192, 512), 8, "xla"),
    ("tpu", (1, 8192, 4096 + 128), 8, "xla"),
    ("tpu", (8192, 4096), 8, "xla"),
], ids=["the_cell_on_a_tpu", "the_cell_on_a_cpu", "one_group",
        "groups_of_64_fill_no_tile", "groups_that_do_not_divide",
        "rows_with_no_batch"])
def test_norm_path_reads_the_backend_and_the_shapes(
        monkeypatch, backend, shape, groups, path):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert norms.norm_path(shape, groups) == path


@pytest.mark.parametrize("axes, batch, path", [
    (None, 1, "xla"),
    ({"dp": 1}, 1, "pallas"),
    ({"dp": 4}, 4, "pallas"),
    ({"dp": 2, "fsdp": 2}, 8, "pallas"),
    ({"dp": 4}, 2, "xla"),
    ({"dp": 2, "tp": 2}, 4, "xla"),
    ({"ep": 2}, 4, "xla"),
], ids=["no_mesh_in_a_process_of_eight_devices", "a_mesh_of_one_device",
        "dp", "dp_and_fsdp", "a_batch_dp_does_not_divide", "dp_and_tp",
        "ep_alone"])
def test_norm_path_reads_the_devices_the_program_spans(
        monkeypatch, axes, batch, path):
    """The norm takes its kernels exactly where the scan does: the two
    tables are one."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = None if axes is None else _mesh(**axes)
    assert norms.norm_path((batch, *NORM_CELL[1:]), 8, mesh) == path
    x, state = ((batch, *shape[1:]) for shape in CELL[:2])
    assert (ssm.scan_path(x, state, 128, mesh) == "pallas_chunked") == (
        path == "pallas")


def test_kernel_norm_over_a_batch_sharded_mesh_is_the_one_device_norm():
    """Under the ``shard_map`` over ``dp`` each device norms its own
    rows; ``scale`` is whole on both and its gradient is the sum of the
    two devices'."""
    mesh = _mesh(dp=2)
    args = _norm_inputs((2, 48, 256), jnp.float32, seed=13)
    want = _value_and_grads(_norm_kernel(2), *args)
    got = jax.jit(lambda *a: _value_and_grads(
        _norm_kernel(2, mesh=mesh, batch_axes=("dp",)), *a))(*args)
    assert got[0].sharding.spec[0] in ("dp", ("dp",))
    for name, g, w in zip("out dy dz dscale".split(), got, want):
        np.testing.assert_allclose(
            g, w, rtol=1e-6, atol=1e-6 * float(jnp.abs(w).max()),
            err_msg=name)


def test_columns_the_norms_kernels_do_not_tile_are_refused_by_name():
    with pytest.raises(ValueError, match="do not tile"):
        _norm_kernel(3)(*_norm_inputs((2, 16, 12), jnp.float32)[:3])


# ---------------------------------------------------------------------------
# Kimi Delta Attention's output gate: the file's second kernel pair,
# interpreted here, against the XLA function (which this backend's
# ``sigmoid_gated_head_rms_norm`` is)
# ---------------------------------------------------------------------------

def _gate_inputs(shape, heads, dtype, seed=0):
    """o, gate, scale [C / heads], dout."""
    o, gate, _, dout = _norm_inputs(shape, dtype, seed)
    scale = 1.0 + 0.5 * jax.random.normal(jax.random.key(seed + 1),
                                          (shape[-1] // heads,))
    return o, gate, scale, dout


def _gate_kernel(heads, **kw):
    return lambda *a: gated_norm.head_gate_norm(
        *a, heads=heads, eps=1e-5, interpret=True, **kw)


# (batch, T, C), heads: row blocks of 128 rows
GATE_CASES = {
    "one_head_a_ragged_last_block": ((2, 300, 128), 1),
    "eight_heads_a_ragged_last_block": ((2, 300, 1024), 8),
    "four_heads_of_two_tiles_whole_blocks": ((2, 256, 1024), 4),
    "fewer_rows_than_a_strip_holds": ((2, 7, 256), 2),
}


@pytest.mark.parametrize("gate_fn", ["sigmoid", "silu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", GATE_CASES)
def test_kernel_output_gate_is_the_xla_output_gate(monkeypatch, case, dtype,
                                                   gate_fn):
    """The value and all three gradients, as
    ``test_kernel_norm_is_the_xla_norm`` holds the other form:
    ``scale``'s gradient is a float32 sum over the rows and the heads
    either way. Under either gate's function: Kimi Delta Attention's
    sigmoid and Gated DeltaNet's ``silu`` are a kernel pair each."""
    shape, heads = GATE_CASES[case]
    monkeypatch.setattr(gated_norm, "_BLOCK_BYTES",
                        128 * shape[-1] * jnp.dtype(dtype).itemsize)
    args = _gate_inputs(shape, heads, dtype, seed=shape[1])
    got = _value_and_grads(_gate_kernel(heads, gate_fn=gate_fn), *args)
    want = _value_and_grads(
        lambda *a: norms.sigmoid_gated_head_rms_norm(
            *a, heads, 1e-5, gate_fn=gate_fn), *args)
    step = 1e-5 if dtype == jnp.float32 else 2 ** -7
    for name, g, w in zip("out do dgate dscale".split(), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        tol = 1e-5 if name == "dscale" else step
        np.testing.assert_allclose(
            g, w, rtol=tol, atol=tol * float(jnp.abs(w).max()),
            err_msg=name)


def test_kernel_output_gate_takes_its_dtype_from_the_gate():
    """``o`` float32 from a recurrence and a bfloat16 ``gate``: the
    result and ``dgate`` are the gate's, ``do`` is ``o``'s, as the XLA
    function's."""
    o, gate, scale, dout = _gate_inputs((1, 40, 256), 2, jnp.float32, seed=3)
    gate, dout = gate.astype(jnp.bfloat16), dout.astype(jnp.bfloat16)
    got = _value_and_grads(_gate_kernel(2), o, gate, scale, dout)
    want = _value_and_grads(
        lambda *a: norms.sigmoid_gated_head_rms_norm(*a, 2, 1e-5),
        o, gate, scale, dout)
    for name, g, w in zip("out do dgate dscale".split(), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        w = w.astype(jnp.float32)
        np.testing.assert_allclose(
            g.astype(jnp.float32), w, rtol=2 ** -7,
            atol=2 ** -7 * float(jnp.abs(w).max()), err_msg=name)


KDA_GATE_CELL = (1, 16384, 4096)


@pytest.mark.parametrize("backend, shape, heads, path", [
    ("tpu", KDA_GATE_CELL, 32, "pallas"),
    ("cpu", KDA_GATE_CELL, 32, "xla"),
    ("tpu", (1, 64, 32), 2, "xla"),
    ("tpu", (16384, 4096), 32, "xla"),
], ids=["the_cell_on_a_tpu", "the_cell_on_a_cpu",
        "the_tiny_models_heads_of_16", "rows_with_no_batch"])
def test_norm_path_serves_32_heads_of_128(monkeypatch, backend, shape, heads,
                                          path):
    """The output gate asks ``norm_path`` with a head a group, and notes
    the answer for the trace."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert norms.norm_path(shape, heads) == path


@pytest.mark.parametrize("axes, batch, path", [
    ({"dp": 4}, 4, "pallas"), ({"dp": 2, "tp": 2}, 4, "xla")],
    ids=["dp", "dp_and_tp"])
def test_output_gate_notes_the_path_a_mesh_gives_it(monkeypatch, axes, batch,
                                                    path):
    """``sigmoid_gated_head_rms_norm`` hands its mesh to ``norm_path``
    and notes ``kda_gate_path``; nothing else chooses."""
    from ray_tpu.util import tracing
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh, seen = _mesh(**axes), {}
    monkeypatch.setattr(tracing, "note_trace", seen.update)
    monkeypatch.setattr(gated_norm, "head_gate_norm",
                        lambda o, *a, **kw: ("kernels", kw["batch_axes"]))
    monkeypatch.setattr(norms, "_sigmoid_gated_head_rms_norm_xla",
                        lambda *a: ("xla", None))
    o = jax.ShapeDtypeStruct((batch, 256, 4096), jnp.bfloat16)
    ran, batch_axes = norms.sigmoid_gated_head_rms_norm(
        o, o, None, 32, 1e-5, mesh=mesh)
    assert seen == {"kda_gate_path": path}
    assert (ran, batch_axes) == (("kernels", ("dp",)) if path == "pallas"
                                 else ("xla", None))
    seen.clear()    # Gated DeltaNet's gate: the same rule, its own note
    assert norms.sigmoid_gated_head_rms_norm(
        o, o, None, 32, 1e-5, mesh=mesh, gate_fn="silu")[0] == ran
    assert seen == {"gdn_gate_path": path}


def test_kernel_output_gate_over_a_batch_sharded_mesh_is_the_one_device_gate():
    """As the other form's: each device gates its own rows, and the
    shared ``scale``'s gradient is the sum of the two devices'."""
    mesh = _mesh(dp=2)
    args = _gate_inputs((2, 48, 256), 2, jnp.float32, seed=13)
    want = _value_and_grads(_gate_kernel(2), *args)
    got = jax.jit(lambda *a: _value_and_grads(
        _gate_kernel(2, mesh=mesh, batch_axes=("dp",)), *a))(*args)
    assert got[0].sharding.spec[0] in ("dp", ("dp",))
    for name, g, w in zip("out do dgate dscale".split(), got, want):
        np.testing.assert_allclose(
            g, w, rtol=1e-6, atol=1e-6 * float(jnp.abs(w).max()),
            err_msg=name)


def test_heads_the_output_gates_kernels_do_not_tile_are_refused_by_name():
    with pytest.raises(ValueError, match="do not tile"):
        _gate_kernel(2)(*_gate_inputs((2, 16, 32), 2, jnp.float32)[:3])


# ---------------------------------------------------------------------------
# the convolution's kernels, interpreted here, against the XLA function
# (which this backend's ``causal_conv1d_silu`` is)
# ---------------------------------------------------------------------------

def _conv_inputs(shape, dtype, bias=True, taps=4, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    x, dy = (jax.random.normal(k, shape).astype(dtype) for k in ks[:2])
    w = jax.random.uniform(ks[2], (taps, shape[-1]), minval=-0.5, maxval=0.5)
    b = (jax.random.uniform(ks[3], shape[-1:], minval=-0.5, maxval=0.5)
         if bias else None)
    return x, w, b, dy


def _conv_kernel(**kw):
    return lambda *a: causal_conv.causal_conv(*a, interpret=True, **kw)


@functools.partial(jax.jit, static_argnums=0)
def _conv_value_and_grads(f, x, w, b, dy):
    """(y, dx, dw, dbias), the last left out where there is no bias."""
    if b is None:
        out, vjp = jax.vjp(lambda x, w: f(x, w, None), x, w)
    else:
        out, vjp = jax.vjp(f, x, w, b)
    return (out, *vjp(dy))


# (batch, T, C), the kernels' blocks: row blocks of 64 rows in strips of
# 32 unless the case says otherwise
CONV_CASES = {
    "whole_blocks": ((2, 128, 128), {}),
    "a_ragged_tail": ((2, 150, 128), {}),
    "a_tail_of_whole_dead_strips": ((1, 70, 128), {}),
    "shorter_than_one_block": ((2, 7, 128), {}),
    "three_lane_blocks": ((2, 150, 384), {"lanes": 128}),
    "a_strip_of_two_lane_slices": ((1, 96, 256), {"width": 128}),
    "one_strip_a_block": ((1, 100, 256), {"strip": 64, "width": 256}),
}


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CONV_CASES)
def test_kernel_conv_is_the_xla_conv(case, dtype, bias):
    """The value and every gradient, in the operands' dtypes. The
    kernels are float32 inside and round once on the way out; the XLA
    function multiplies and adds in ``x``'s dtype. So in float32 the two
    differ by the order of the sums; in bfloat16 the kernels are held to
    one step of the result's rounding from the XLA function *computed in
    float32 from the same bfloat16 rows*, and the XLA function in
    bfloat16 to what its own rounding of every product allows."""
    shape, blocks = CONV_CASES[case]
    blocks = {"rows": 64, "strip": 32, **blocks}
    args = _conv_inputs(shape, dtype, bias, seed=shape[1])
    got = _conv_value_and_grads(_conv_kernel(**blocks), *args)
    want = _conv_value_and_grads(conv1d._causal_conv1d_silu_xla, *args)
    f32 = jnp.float32
    exact = _conv_value_and_grads(
        conv1d._causal_conv1d_silu_xla,
        *(None if a is None else a.astype(f32) for a in args))
    assert len(got) == len(want) == (4 if bias else 3)
    for name, g, w, e in zip("y dx dw dbias".split(), got, want, exact):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g, w = g.astype(f32), w.astype(f32)
        top = float(jnp.abs(e).max())
        # dw and dbias leave the kernels as float32 sums
        step = 1e-5 if dtype == f32 or name in ("dw", "dbias") else 2 ** -8
        np.testing.assert_allclose(g, e, rtol=step, atol=step * top,
                                   err_msg=name)
        np.testing.assert_allclose(
            g, w, rtol=1e-5 if dtype == f32 else 2 ** -5,
            atol=(1e-5 if dtype == f32 else 2 ** -5) * top, err_msg=name)


def test_kernel_conv_carries_the_halo_across_row_blocks_both_ways():
    """One non-zero row each side of a block boundary: the forward's
    rows below the boundary read the taps' products of the row above
    it, and the backward hands the cotangents of the rows below it up,
    each tap at its own distance and the first ``K - 1`` rows of the
    sequence seeing zeros before them."""
    t, c, rows = 128, 128, 64
    w = jnp.arange(1.0, 5.0)[:, None] * jnp.ones((4, c))
    kernel = _conv_kernel(rows=rows, strip=32)
    x = jnp.zeros((1, t, c)).at[0, rows - 1].set(1.0).at[0, 0].set(2.0)
    z = np.zeros((t,), np.float32)
    z[rows - 1:rows + 3] = [4.0, 3.0, 2.0, 1.0]     # w[3], w[2], w[1], w[0]
    z[0:4] = [8.0, 6.0, 4.0, 2.0]
    np.testing.assert_allclose(kernel(x, w, None)[0, :, 0],
                               z / (1 + np.exp(-z)), rtol=1e-6)
    # silu'(0) = 1/2: dx[t] = sum_j w[j] / 2 * dy[t + 3 - j]
    dy = jnp.zeros((1, t, c)).at[0, rows + 1].set(2.0)
    dx = jax.vjp(lambda x: kernel(x, w, None), jnp.zeros((1, t, c)))[1](dy)[0]
    want = np.zeros((t,), np.float32)
    want[rows - 2:rows + 2] = [1.0, 2.0, 3.0, 4.0]
    np.testing.assert_allclose(dx[0, :, 0], want, rtol=1e-6)


def test_kernel_conv_is_causal():
    """Later rows do not change earlier outputs, across a row block's
    boundary too."""
    x, w, b, _ = _conv_inputs((2, 100, 128), jnp.float32, seed=3)
    kernel = _conv_kernel(rows=64, strip=32)
    later = x.at[:, 70:].set(0.0)
    np.testing.assert_array_equal(kernel(later, w, b)[:, :70],
                                  kernel(x, w, b)[:, :70])


def test_kernel_conv_keeps_x_and_no_float32_rows():
    """What the backward keeps: ``x`` in its own dtype beside the taps
    and the bias, as the XLA function's checkpoint does."""
    from jax._src.ad_checkpoint import saved_residuals
    x, w, b, _ = _conv_inputs((2, 64, 128), jnp.bfloat16)
    saved = saved_residuals(
        lambda *a: _conv_kernel()(*a).astype(jnp.float32).sum(), x, w, b)
    rows = [aval for aval, _ in saved if aval.shape[:2] == x.shape[:2]]
    assert [(r.shape, r.dtype) for r in rows] == [(x.shape, x.dtype)]


CONV_CELL = (1, 16384, 4096)


@pytest.mark.parametrize("backend, shape, taps, path", [
    ("tpu", CONV_CELL, 4, "pallas"),
    ("cpu", CONV_CELL, 4, "xla"),
    ("tpu", (1, 8192, 6144), 4, "pallas"),
    ("tpu", (2, 64, 96), 4, "xla"),
    ("tpu", (1, 8192, 4096 + 64), 4, "xla"),
    ("tpu", CONV_CELL, 18, "xla"),
    ("tpu", CONV_CELL[1:], 4, "xla"),
], ids=["the_cell_on_a_tpu", "the_cell_on_a_cpu", "nemotrons_cell",
        "a_tiny_presets_width", "half_a_lane_tile_over",
        "taps_that_reach_past_a_row_tile", "rows_with_no_batch"])
def test_conv_path_reads_the_backend_and_the_shapes(
        monkeypatch, backend, shape, taps, path):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert conv1d.conv_path(shape, taps) == path


@pytest.mark.parametrize("axes, batch, path", [
    (None, 1, "xla"),
    ({"dp": 1}, 1, "pallas"),
    ({"dp": 4}, 4, "pallas"),
    ({"dp": 2, "fsdp": 2}, 8, "pallas"),
    ({"dp": 4}, 2, "xla"),
    ({"dp": 2, "tp": 2}, 4, "xla"),
    ({"sp": 2}, 4, "xla"),
], ids=["no_mesh_in_a_process_of_eight_devices", "a_mesh_of_one_device",
        "dp", "dp_and_fsdp", "a_batch_dp_does_not_divide", "dp_and_tp",
        "sp_alone"])
def test_conv_path_reads_the_devices_the_program_spans(
        monkeypatch, axes, batch, path):
    """The convolution takes its kernels where the norm does: a program
    of one device, or one whose mesh shards the batch and nothing
    else."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = None if axes is None else _mesh(**axes)
    assert conv1d.conv_path((batch, *CONV_CELL[1:]), 4, mesh) == path


@pytest.mark.parametrize("backend, path", [("cpu", "xla"), ("tpu", "pallas")])
def test_conv_notes_its_path_and_takes_it(monkeypatch, backend, path):
    """``causal_conv1d_silu`` notes what ``conv_path`` said for the
    trace in progress and calls that path: the kernels are handed the
    operands and the axes the batch is sharded over."""
    from ray_tpu.util import tracing
    notes, calls = {}, []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    monkeypatch.setattr(
        causal_conv, "causal_conv", lambda x, *a, **kw: calls.append(kw) or x)
    x, w, b, _ = _conv_inputs((1, 32, 256), jnp.float32)
    y = conv1d.causal_conv1d_silu(x, w, b)
    assert notes == {"conv_path": path, "conv_taps": 4, "conv_cols": 256}
    assert calls == ([{"mesh": None, "batch_axes": ()}] if path == "pallas"
                     else [])
    if path == "xla":
        np.testing.assert_array_equal(
            y, conv1d._causal_conv1d_silu_xla(x, w, b))


def test_kernel_conv_over_a_batch_sharded_mesh_is_the_one_device_conv():
    """Under the ``shard_map`` over ``dp`` each device convolves its own
    sequences; the taps and the bias are whole on both and their
    gradients are the sum of the two devices'."""
    mesh = _mesh(dp=2)
    args = _conv_inputs((2, 48, 256), jnp.float32, seed=17)
    want = _conv_value_and_grads(_conv_kernel(), *args)
    got = jax.jit(lambda *a: _conv_value_and_grads(
        _conv_kernel(mesh=mesh, batch_axes=("dp",)), *a))(*args)
    assert got[0].sharding.spec[0] in ("dp", ("dp",))
    for name, g, w in zip("y dx dw dbias".split(), got, want):
        np.testing.assert_allclose(
            g, w, rtol=1e-6, atol=1e-6 * float(jnp.abs(w).max()),
            err_msg=name)


@pytest.mark.parametrize("shape, taps", [((2, 16, 96), 4), ((2, 16, 128), 18)],
                         ids=["columns", "taps"])
def test_what_the_convs_kernels_do_not_tile_is_refused_by_name(shape, taps):
    x, w, b, _ = _conv_inputs(shape, jnp.float32, taps=taps)
    with pytest.raises(ValueError, match=f"do not tile {shape[-1]} columns "
                       f"at {taps} taps"):
        _conv_kernel()(x, w, b)

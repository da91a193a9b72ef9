"""``ops/cca.py``: the pieces between CCA's projections and its kernel,
each against something that shares no code with it (``lax``'s own
convolution, ``models/llama.py``'s rotation, a sum written out), their
causality, and the backward pass that runs them again; then the kernels
that take the passes' place on a TPU (``ops/pallas/cca_mix.py``),
interpreted here, against those same pieces, and ``cca_path``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import apply_rope_half, rope_freqs
from ray_tpu.ops import cca
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.pallas import cca_mix

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


# ---------------------------------------------------------------------------
# the kernels, interpreted here, against the XLA passes
# ---------------------------------------------------------------------------

KD, KC = 128, (H + G) * 128     # a head of one 128-lane tile
ROWS, STRIP = 32, 16            # of a block and a strip here
# T, taps, dtype: blocks of 32 rows walked 16 at a time
KERNEL_CASES = {
    "three_whole_blocks": (96, (2, 2), jnp.float32),
    "a_last_block_the_rows_do_not_fill": (80, (2, 2), jnp.float32),
    "taps_of_3_and_4": (72, (3, 4), jnp.float32),
    "fewer_rows_than_a_strip": (11, (2, 2), jnp.float32),
    "bfloat16": (96, (2, 2), jnp.bfloat16),
}
OUTPUTS = ("q", "k", "qk", "conv0_w", "conv0_b", "conv1_w", "conv1_b", "tau")


def _kernel_operands(t, taps, dtype, seed=0):
    """Biases far from zero and ``tau`` far from one: a zeroed
    block-first row, or ``conv0``'s bias read as ``conv1``'s row -1,
    moves the result by far more than the comparison allows."""
    return dict(
        qk=_rand(seed, B, t, KC).astype(dtype),
        conv0=(_rand(seed + 2, taps[0], KC, scale=0.5),
               _rand(seed + 3, KC, scale=0.5)),
        conv1=(_rand(seed + 4, taps[1], H + G, KD, KD, scale=0.1),
               _rand(seed + 5, KC, scale=0.5)),
        tau=1.5 + _rand(seed + 6, G, scale=0.3),
        angles=rope_freqs(KD // 2, t + 3, 10000.0),
        dq=_rand(seed + 7, B, t, H, KD).astype(dtype),
        dk=_rand(seed + 8, B, t, G, KD).astype(dtype))


def _small_blocks(monkeypatch):
    monkeypatch.setattr(cca_mix, "_BLOCK_ROWS", ROWS)
    monkeypatch.setattr(cca_mix, "_STRIP", STRIP)


def _kernel(ops, **kw):
    return lambda *a: cca_mix.cca_mix(*a, ops["angles"], n_head=H,
                                      n_kv_head=G, interpret=True, **kw)


def _xla(ops):
    return lambda *a: cca._qk_for_kernel(*a, ops["angles"], H, G)


def _values_and_cotangents(f, ops):
    """``OUTPUTS``, in order."""
    (q, k), pull = jax.vjp(f, ops["qk"], ops["conv0"], ops["conv1"],
                           ops["tau"])
    dqk, (dw0, db0), (dw1, db1), dtau = pull((ops["dq"], ops["dk"]))
    return dict(zip(OUTPUTS, (q, k, dqk, dw0, db0, dw1, db1, dtau)))


@functools.lru_cache(maxsize=None)
def _both(case):
    t, taps, dtype = KERNEL_CASES[case]
    ops = _kernel_operands(t, taps, dtype)
    with pytest.MonkeyPatch.context() as mp:
        _small_blocks(mp)
        got = _values_and_cotangents(_kernel(ops), ops)
    # this backend's dot takes no bfloat16 operands: the XLA passes get
    # the same values in float32
    ops = jax.tree.map(lambda x: x.astype(jnp.float32), ops)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda o: _values_and_cotangents(_xla(o), o))(ops)
    return got, want


@pytest.mark.parametrize("name", OUTPUTS)
@pytest.mark.parametrize("case", KERNEL_CASES)
def test_kernels_are_the_xla_passes(case, name):
    """``q``, ``k`` and the six cotangents of ``jax.vjp`` straight
    through ``_qk_for_kernel``. In float32 the two differ by the order
    of their sums. The bfloat16 kernels (the MXU's operands, ``q``,
    ``k`` and ``d[q~ | k~]`` in bfloat16, float32 between) stand
    against the float32 passes on the same values: a few steps of
    bfloat16's rounding."""
    got, want = (x[name] for x in _both(case))
    dtype = KERNEL_CASES[case][2]
    low = name in ("q", "k", "qk")
    assert got.dtype == (dtype if low else jnp.float32)
    assert got.shape == want.shape
    got = got.astype(jnp.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -5
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * float(jnp.abs(want).max()))


@pytest.mark.parametrize("taps", [(2, 2), (3, 4)])
def test_a_change_at_row_t_moves_no_kernel_output_before_t_or_in_the_other_sequence(
        monkeypatch, taps):
    """Row 40 is a block's ninth: the rows before it, across the
    block's edge at 32 too, and the whole of the other batch element
    are bit for bit what they were; the rows from it on are not."""
    _small_blocks(monkeypatch)
    ops = _kernel_operands(80, taps, jnp.float32)
    at = 40
    moved = ops["qk"].at[1, at].add(1.0)
    args = (ops["conv0"], ops["conv1"], ops["tau"])
    a = _kernel(ops)(ops["qk"], *args)
    b = _kernel(ops)(moved, *args)
    reach = sum(taps) - 2
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x[0], y[0])
        np.testing.assert_array_equal(x[1, :at], y[1, :at])
        assert float(jnp.abs(x[1, at + reach] - y[1, at + reach]).max()) > 0
        np.testing.assert_array_equal(x[1, at + reach + 1:],
                                      y[1, at + reach + 1:])


def test_a_sequences_first_rows_read_zeros_and_a_blocks_first_rows_do_not(
        monkeypatch):
    """Two sequences that differ only in their first 32 rows: the rows
    of the second block that reach back over the edge differ, so a
    block's first rows read the rows before them; and row 0 is what
    the XLA passes give with zeros before it, ``conv0``'s bias
    included (``test_kernels_are_the_xla_passes`` holds it to that)."""
    _small_blocks(monkeypatch)
    ops = _kernel_operands(64, (2, 2), jnp.float32)
    other = ops["qk"].at[:, :ROWS].multiply(-1.0)
    args = (ops["conv0"], ops["conv1"], ops["tau"])
    a, b = _kernel(ops)(ops["qk"], *args), _kernel(ops)(other, *args)
    for x, y in zip(a, b):
        assert float(jnp.abs(x[:, ROWS] - y[:, ROWS]).max()) > 1e-3
        assert float(jnp.abs(x[:, ROWS + 1] - y[:, ROWS + 1]).max()) > 1e-3
        np.testing.assert_array_equal(x[:, ROWS + 2:], y[:, ROWS + 2:])


def _mesh(**axes):
    from ray_tpu.parallel.mesh import make_mesh
    size = int(np.prod(list(axes.values())))
    return make_mesh(axes, devices=jax.devices()[:size])


def test_kernels_over_a_batch_sharded_mesh_are_the_one_device_kernels(
        monkeypatch):
    """Under the ``shard_map`` over ``dp`` each device mixes its own
    sequence; the weights are whole on both and their cotangents are
    the sum of the two devices'."""
    _small_blocks(monkeypatch)
    ops = _kernel_operands(48, (2, 2), jnp.float32, seed=20)
    mesh = _mesh(dp=2)
    want = _values_and_cotangents(_kernel(ops), ops)
    got = jax.jit(lambda o: _values_and_cotangents(
        _kernel(o, mesh=mesh, batch_axes=("dp",)), o))(ops)
    assert got["q"].sharding.spec[0] in ("dp", ("dp",))
    for name in OUTPUTS:
        np.testing.assert_allclose(
            got[name], want[name], rtol=1e-6,
            atol=1e-6 * float(jnp.abs(want[name]).max()), err_msg=name)


def test_shapes_the_kernels_do_not_tile_are_refused_by_name():
    ops = _operands()       # heads of 16 lanes
    with pytest.raises(ValueError, match="do not tile"):
        cca_mix.cca_mix(ops["qk"], ops["conv0"], ops["conv1"], ops["tau"],
                        ops["angles"], n_head=H, n_kv_head=G, interpret=True)


CELL = (2, 8192, 1280)      # zaya1-8b.b2-t8192: 8 + 2 heads of 128


@pytest.mark.parametrize("backend, shape, heads, taps, path", [
    ("tpu", CELL, (8, 2), (2, 2), "pallas"),
    ("cpu", CELL, (8, 2), (2, 2), "xla"),
    ("tpu", (2, 8192, 640), (8, 2), (2, 2), "xla"),
    ("tpu", (2, 8192, 1920), (8, 2), (2, 2), "xla"),
    ("tpu", (2, 8192, 2560), (8, 2), (2, 2), "pallas"),
    ("tpu", CELL, (8, 2), (4, 4), "pallas"),
    ("tpu", CELL, (8, 2), (9, 10), "xla"),
    ("tpu", (2, 1000, 1280), (8, 2), (2, 2), "pallas"),
    ("tpu", CELL, (7, 3), (2, 2), "xla"),
    ("tpu", (8192, 1280), (8, 2), (2, 2), "xla"),
], ids=["the_cell_on_a_tpu", "the_cell_on_a_cpu", "heads_of_64_fill_no_tile",
        "heads_of_192", "heads_of_256", "taps_of_4_and_4",
        "taps_that_reach_past_a_sublane_tile", "rows_that_fill_no_block",
        "heads_in_no_whole_groups", "rows_with_no_batch"])
def test_cca_path_names_what_runs_the_passes(
        monkeypatch, backend, shape, heads, taps, path):
    """From the backend, the heads' width, the taps and the rows; and
    the notes beside it say the kernels' block and halo where they
    run."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert cca.cca_path(shape, *heads, taps) == path
    notes = cca.path_notes(shape, *heads, taps)
    if path == "xla":
        assert notes == {"cca_path": "xla"}
    else:
        assert notes == {"cca_path": "pallas",
                         "cca_rows_per_block": min(512, -(-shape[1] // 128)
                                                   * 128),
                         "cca_halo_rows": sum(taps) - 2}


@pytest.mark.parametrize("axes, batch, path", [
    (None, 2, "xla"),
    ({"dp": 1}, 2, "pallas"),
    ({"dp": 4}, 4, "pallas"),
    ({"dp": 2, "fsdp": 2}, 8, "pallas"),
    ({"dp": 4}, 2, "xla"),
    ({"sp": 2}, 2, "xla"),
    ({"dp": 2, "sp": 2}, 4, "xla"),
    ({"dp": 2, "tp": 2}, 4, "xla"),
], ids=["no_mesh_in_a_process_of_eight_devices", "a_mesh_of_one_device",
        "dp", "dp_and_fsdp", "a_batch_dp_does_not_divide", "sp",
        "dp_and_sp", "dp_and_tp"])
def test_cca_path_reads_the_devices_the_program_spans(
        monkeypatch, axes, batch, path):
    """As the scan's and the gated norm's kernels (``ops/pallas/
    program.py::batch_axes``): one device bare, a mesh that shards the
    batch alone under a ``shard_map``; a sequence split over chips
    needs a halo across them, which is not there."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.device_count() > 1
    mesh = None if axes is None else _mesh(**axes)
    assert cca.cca_path((batch, *CELL[1:]), 8, 2, (2, 2), mesh) == path


def test_cca_attention_on_this_backend_is_the_xla_passes_and_their_barrier():
    """Where ``cca_path`` says ``xla`` the program is the one before the
    kernels: ``mixed_qk``, with its barrier, and no custom call."""
    ops = _operands()
    text = jax.jit(jax.grad(lambda qk: _o({**ops, "qk": qk}).sum())).lower(
        ops["qk"]).as_text()
    assert "optimization_barrier" in text and "custom_call" not in text

"""Latent attention: the flash kernels at keys of 128 + 64 and values of
128 (``ops/pallas/flash_attention.py``'s last section), interpreted on
the CPU, against a masked softmax over the concatenated keys; and
``ops/mla.py::latent_attention``, whose backward pass may keep the
latents and run the up-projections again."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import apply_rope, rope_freqs
from ray_tpu.ops import mla
from ray_tpu.ops.pallas.flash_attention import (
    mla_flash_core, mla_flash_shapes_ok, mla_flash_static,
)
from ray_tpu.parallel import make_mesh
from ray_tpu.util import tracing

# the package exports the function under the module's name
fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")

B, T, H, DN, DR = 2, 384, 4, 128, 64


def _concatenated(qn, qr, kn, kr, v):
    """Head i: softmax(mask([qn_i | qr_i] [kn_i | kr]^T / sqrt(192))) v_i,
    the one rotary key copied to every head."""
    b, t, h, _ = qn.shape
    q = jnp.concatenate([qn, qr], -1)
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(kr[:, :, None], (b, t, h, kr.shape[-1]))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _kernels(qn, qr, kn, kr, v, block):
    """``mla_flash_core`` on the heads' [B, T, H, D] operands, folded to
    the [B, T, H*D] it indexes as ``ops/mla.py``'s matmuls write it."""
    b, t, h, dn = qn.shape
    static = mla_flash_static(t, dn, qr.shape[-1], block=block,
                              interpret=True)
    out = mla_flash_core(qn.reshape(b, t, -1), qr.reshape(b, t, -1),
                         kn.reshape(b, t, -1), kr, v.reshape(b, t, -1),
                         static)
    return out.reshape(b, t, h, dn)


def _operands(seed=0, t=T, h=H):
    ks = jax.random.split(jax.random.key(seed), 6)
    return ((jax.random.normal(ks[0], (B, t, h, DN)),
             jax.random.normal(ks[1], (B, t, h, DR)),
             jax.random.normal(ks[2], (B, t, h, DN)),
             jax.random.normal(ks[3], (B, t, DR)),
             jax.random.normal(ks[4], (B, t, h, DN))),
            jax.random.normal(ks[5], (B, t, h, DN)))


@pytest.mark.parametrize("block", [128, 384], ids=["three_blocks", "one"])
def test_kernel_forward_is_the_masked_softmax_over_concatenated_keys(block):
    ops, _ = _operands()
    got = _kernels(*ops, block=block)
    np.testing.assert_allclose(got, _concatenated(*ops), atol=2e-5)


FIVE = [("dq_nope", 0), ("dq_rope", 1), ("dk_nope", 2), ("dk_rope", 3),
        ("dv", 4)]


def _gradient(fn, ops, w, arg):
    return jax.grad(lambda *a: (fn(*a) * w).sum(), argnums=arg)(*ops)


@pytest.mark.parametrize("block", [384, 192, 128],
                         ids=["one_block", "two_blocks", "three_blocks"])
@pytest.mark.parametrize("name, arg", FIVE)
def test_kernel_backward_gives_each_of_the_five_gradients(name, arg, block):
    """The backward kernel: where only the diagonal block is live (one
    block), where one block lies under it, and three blocks. ``dq`` is
    carried in scratch across the key blocks and leaves at its diagonal;
    ``dk_rope`` is the sum over the heads and, in the kernel, over the
    head pairs: carried across the grid's second dimension."""
    ops, w = _operands(1)
    got = _gradient(lambda *a: _kernels(*a, block=block), ops, w, arg)
    want = _gradient(_concatenated, ops, w, arg)
    assert got.shape == ops[arg].shape
    np.testing.assert_allclose(got, want, atol=3e-5 * float(
        jnp.abs(want).max()) + 1e-6, err_msg=name)


def test_the_backward_kernels_gradients_are_the_same_run_to_run():
    """What ``correct`` leans on: every sum runs in the grid's order
    (key blocks into ``dq``, q-blocks into ``dk_n`` / ``dv``, then the
    head pairs into ``dk_r``), so two compiles of one program give the
    same bits: eight heads (four head pairs), two rows of the batch,
    two blocks."""
    ops, w = _operands(5, t=256, h=8)

    def run():
        jax.clear_caches()
        return jax.grad(lambda *a: (_kernels(*a, block=128) * w).sum(),
                        argnums=(0, 1, 2, 3, 4))(*ops)
    for (name, _), a, b in zip(FIVE, run(), run()):
        np.testing.assert_array_equal(a, b, err_msg=name)


MiB = 1 << 20


@pytest.mark.parametrize("t, dn, dr, limit, fits", [
    (8192, 128, 64, None, True),            # the cell
    (32768, 128, 64, None, True),           # 64 MiB of accumulators
    (65536, 128, 64, None, False),
    (8192, 128, 64, 64 * MiB, True),
    (32768, 128, 64, 64 * MiB, False),      # the limit decides
    (16384, 256, 64, None, True),
    (32768, 256, 64, None, False),          # the widths decide
    (384, 128, 64, 1 * MiB, False),
], ids=lambda v: str(v))
def test_a_row_past_the_backward_kernels_budget_is_refused_by_name(
        t, dn, dr, limit, fits, monkeypatch):
    """``mla_flash_static`` notes how many rows of ``dq`` the backward
    kernel keeps in VMEM; where they do not fit ``_MLA_BWD_VMEM`` it
    raises ``NotImplementedError`` with the rows, the lanes of a head
    pair, the bytes and the budget, and notes nothing."""
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    if limit is not None:
        monkeypatch.setattr(fa, "_MLA_BWD_VMEM", limit)
    if not fits:
        asked = fa._mla_bwd_bytes(t, fa._pick_block(t), dn, dr)
        with pytest.raises(NotImplementedError) as refused:
            mla_flash_static(t, dn, dr)
        for part in (f"{t} rows", f"{2 * (dn + dr)} lanes",
                     f"{asked} bytes", f"budget of {fa._MLA_BWD_VMEM}",
                     "`sp` mesh axis"):
            assert part in str(refused.value), part
        assert not notes
        return
    assert mla_flash_static(t, dn, dr).block == fa._pick_block(t)
    assert notes["flash_bwd_resident_rows"] == t
    assert notes["flash_path"] == "mla_multi_block"


def test_the_shapes_the_kernels_tile():
    assert mla_flash_shapes_ok(8192, 128, 64, 128, 32)     # the cell's
    assert not mla_flash_shapes_ok(8192, 128, 64, 64, 32)  # v != nope
    assert not mla_flash_shapes_ok(8192, 128, 64, 128, 3)  # an odd head
    assert not mla_flash_shapes_ok(64, 128, 64, 128, 4)    # a short row
    assert not mla_flash_shapes_ok(8192, 16, 8, 16, 4)     # the tiny preset
    with pytest.raises(ValueError, match="not divisible into flash blocks"):
        mla_flash_static(100, 128, 64)


# -- ops/mla.py ------------------------------------------------------------

RQ, RKV = 32, 16


def _latents(seed=2, t=256, h=2):
    ks = jax.random.split(jax.random.key(seed), 8)
    up = mla.UpProjections(
        jax.random.normal(ks[3], (RQ, h * DN)) * 0.2,
        jax.random.normal(ks[4], (RQ, h * DR)) * 0.2,
        jax.random.normal(ks[5], (RKV, h * DN)) * 0.2,
        jax.random.normal(ks[6], (RKV, h * DN)) * 0.2)
    return (jax.random.normal(ks[0], (B, t, RQ)),
            jax.random.normal(ks[1], (B, t, RKV)),
            jax.random.normal(ks[2], (B, t, DR)), up,
            jax.random.normal(ks[7], (B, t, h * DN)))


def _plain(c_q, c_kv, k_r, up, angles, h):
    """``latent_attention`` written out: up-project, rotate, attend."""
    b, t, _ = c_q.shape
    qn = (c_q @ up.q_nope).reshape(b, t, h, DN)
    qr = apply_rope((c_q @ up.q_rope).reshape(b, t, h, DR), angles)
    kn = (c_kv @ up.k_nope).reshape(b, t, h, DN)
    v = (c_kv @ up.v).reshape(b, t, h, DN)
    kr = apply_rope(k_r[:, :, None], angles)[:, :, 0]
    return _concatenated(qn, qr, kn, kr, v).reshape(b, t, h * DN)


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernels_interpreted", "xla_path"])
def test_recomputed_up_projections_give_the_gradients_of_kept_ones(
        interpret):
    """``saved="latents"`` (the kernels' operands made again in the
    backward pass from ``c_q``, ``c_kv``, ``k_r``) against
    ``saved="expanded"`` and against the attention written out: the
    output and the gradient of every operand, weights included."""
    c_q, c_kv, k_r, up, w = _latents()
    angles = rope_freqs(DR, 256, 10000.0)

    def loss(saved):
        def f(c_q, c_kv, k_r, up):
            o = (_plain(c_q, c_kv, k_r, up, angles, 2) if saved is None
                 else mla.latent_attention(
                     c_q, c_kv, k_r, up, angles, n_head=2, saved=saved,
                     interpret=interpret))
            return (o * w).sum()
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3))

    with jax.default_matmul_precision("highest"):
        want, wants = loss(None)(c_q, c_kv, k_r, up)
        kept, kepts = loss("expanded")(c_q, c_kv, k_r, up)
        got, gots = loss("latents")(c_q, c_kv, k_r, up)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(kept) == pytest.approx(float(want), rel=1e-5)
    for g, k, x in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (gots, kepts, wants))):
        tol = 1e-4 * float(jnp.abs(x).max())
        np.testing.assert_allclose(g, x, atol=tol)
        np.testing.assert_allclose(g, k, atol=tol)


def test_latents_are_what_the_backward_pass_keeps():
    """With ``saved="latents"`` no residual is as wide as the kernels'
    operands (``H * 128`` a token); with ``"expanded"`` q_nope, k_nope
    and v are."""
    c_q, c_kv, k_r, up, _ = _latents()
    angles = rope_freqs(DR, 256, 10000.0)

    def widths(saved):
        _, pull = jax.vjp(
            lambda *a: mla.latent_attention(
                *a, up, angles, n_head=2, saved=saved, interpret=True).sum(),
            c_q, c_kv, k_r)
        return sorted(x.shape[-1] for x in jax.tree_util.tree_leaves(pull)
                      if x.ndim == 3 and x.shape[1] == 256)

    assert widths("latents").count(2 * DN) == 1          # the output
    assert widths("expanded").count(2 * DN) == 4         # + q, k_nope, v


def test_the_path_is_decided_from_backend_shapes_and_mesh(monkeypatch):
    cell = (1, 8192, 32, 128, 64, 128)
    assert mla.mla_path(*cell) == ("xla", ())            # the CPU
    assert mla.mla_path(*cell, interpret=True) == ("kernel", ())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="no mesh"):
        mla.mla_path(*cell)                  # 8 devices here, no mesh
    one = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    assert mla.mla_path(*cell, mesh=one) == ("kernel", ())
    dp = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    assert mla.mla_path(2, *cell[1:], mesh=dp) == ("kernel", ("dp",))
    assert mla.mla_path(1, *cell[1:], mesh=dp) == ("xla", ())   # init's batch
    assert mla.mla_path(2, 64, 4, 16, 8, 16, mesh=dp) == ("xla", ())
    for axis in ("sp", "tp", "ep"):
        mesh = make_mesh({axis: 2}, devices=jax.devices()[:2])
        with pytest.raises(NotImplementedError, match=f"{axis}=2"):
            mla.mla_path(*cell, mesh=mesh)


def test_the_kernels_under_a_shard_map_over_dp_are_the_bare_ones():
    """Two rows over ``dp`` = 2, kernels interpreted on each device's
    row, against the unsharded call: values and gradients."""
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    c_q, c_kv, k_r, up, w = _latents(3)
    angles = rope_freqs(DR, 256, 10000.0)

    def run(mesh):
        def f(c_q, c_kv, k_r, up):
            return (mla.latent_attention(
                c_q, c_kv, k_r, up, angles, n_head=2, mesh=mesh,
                interpret=True) * w).sum()
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3)))(
            c_q, c_kv, k_r, up)

    assert mla.mla_path(B, 256, 2, DN, DR, DN, mesh, True) == (
        "kernel", ("dp",))
    want, wants = run(None)
    got, gots = run(mesh)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, x in zip(jax.tree_util.tree_leaves(gots),
                    jax.tree_util.tree_leaves(wants)):
        np.testing.assert_allclose(g, x, atol=1e-4 * float(jnp.abs(x).max()))


@pytest.mark.parametrize("mesh_axes", [None, {"dp": 2}],
                         ids=["one_device", "dp2"])
@pytest.mark.parametrize("backend, refused", [("tpu", True), ("cpu", False)],
                         ids=["kernel_path", "xla_path_off_the_tpu"])
def test_latent_attention_refuses_a_row_past_the_budget_on_the_kernel_path(
        backend, refused, mesh_axes, monkeypatch):
    """65,536 rows at the published widths, traced only. Where the
    kernels would run (a TPU, shapes that tile; one device or a batch
    over ``dp``) ``latent_attention`` raises the kernel's refusal before
    anything is traced; off the TPU the XLA path knows no budget and is
    as it was."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    n = 2 if mesh_axes else 1
    mesh = make_mesh(mesh_axes or {"dp": 1}, devices=jax.devices()[:n])
    t, h = 65536, 2
    s = jax.ShapeDtypeStruct
    up = mla.UpProjections(
        s((RQ, h * DN), jnp.bfloat16), s((RQ, h * DR), jnp.bfloat16),
        s((RKV, h * DN), jnp.bfloat16), s((RKV, h * DN), jnp.bfloat16))
    angles = rope_freqs(DR, t, 10000.0)

    def attend(c_q, c_kv, k_r, up):
        return mla.latent_attention(c_q, c_kv, k_r, up, angles, n_head=h,
                                    mesh=mesh)
    args = (s((n, t, RQ), jnp.bfloat16), s((n, t, RKV), jnp.bfloat16),
            s((n, t, DR), jnp.bfloat16), up)
    if refused:
        with pytest.raises(NotImplementedError, match="65536 rows"):
            jax.eval_shape(attend, *args)
    else:
        out = jax.eval_shape(attend, *args)
        assert out.shape == (n, t, h * DN)

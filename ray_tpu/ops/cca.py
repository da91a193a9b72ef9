"""Compressed convolutional attention (CCA) for training.

ZAYA1's attention (arXiv:2510.04476, as ``benchmark/configs/zaya1-8b.json``
writes it down, with what is assumed kept apart): queries and keys are
projected **down** to the heads' own widths (8 query heads and 2
key/value heads of 128 from a hidden size of 2,048) and attention runs
there; nothing is projected up again before the output projection.
Between the projections and the softmax the compressed queries and keys
are mixed over time and with each other. With ``h`` the normed block
input, ``rep`` copying a key/value head to the query heads of its group
and ``groupmean`` the mean of a group's query heads:

    q~ | k~ = h W_qk                      [H*D | G*D]         ``qkv``
    v       = [h W_v1 ; shift(h W_v2)]    [G*D], G = 2: head 0 is the
              token's own value, head 1 the previous token's  ``qkv``
    c       = conv1(conv0([q~ | k~]))     both causal         ``conv``
              conv0 depthwise, conv1 within each head's D channels
    q       = c_q + (q~ + rep(k~)) / 2                        ``mix``
    k       = c_k + (groupmean(q~) + k~) / 2
    q       = sqrt(D) q / |q|,  k = sqrt(D) tau_g k / |k|     ``mix``
    q, k    take RoPE on their first ``rotary`` lanes         ``rope``
    o_i     = causal_softmax(q_i k_g^T / sqrt(D)) v_g         ``core``

``shift(x)_t = x_{t-1}`` with a zero row first; ``shift(h) W = shift(h
W)``, so the shifted half is shifted at 128 wide and not at 2,048. The
projections and the output projection are the model's dense layers
(``models/zaya.py``); this file holds everything between them:
``cca_attention``. The mean, the norm, the temperature and the rotation
are computed in float32 and written once in the compute type; the
grouped convolution is a matmul a tap (bf16 operands, float32 sums).

**Which attention runs.** ``attn_fn`` is the caller's (the models take
theirs from ``ops/attention.py``): the equal-width flash kernel, with
the key/value heads repeated up to the query heads first (4 copies at 8
over 2; the kernel has no grouped form yet). The passes before it,
``conv``, ``mix`` and ``rope``, are ``cca_path``'s decision from what
it can observe: ``pallas`` (``ops/pallas/cca_mix.py``: one kernel
forward and one backward over the row-major ``[q~ | k~]``, their
custom calls under the scope ``mix``) on a TPU where each head is whole
128-lane tiles, the rows a row reads before itself fit one sublane tile
and the program is one the kernels can serve (one device, or a mesh
that shards the batch alone, under a ``shard_map``); else ``xla``:
``_qk_for_kernel`` as XLA's fusions under ``mixed_qk``, which is also
the tests' reference and the CPU's path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas import cca_mix, program


def cca_path(shape, n_head: int, n_kv_head: int, taps, mesh=None) -> str:
    """What computes ``conv``, ``mix`` and ``rope`` for ``[q~ | k~]``
    [B, T, (H + G) * D] at these taps: ``pallas`` on a TPU where the
    kernels tile the shapes and ``program.batch_axes`` finds the
    program one they can serve (one device, or a mesh that shards the
    batch alone), else ``xla``."""
    if (jax.default_backend() == "tpu" and len(shape) == 3
            and cca_mix.shapes_ok(shape[-1], n_head, n_kv_head, taps)
            and program.batch_axes(mesh, shape[0]) is not None):
        return "pallas"
    return "xla"


def path_notes(shape, n_head: int, n_kv_head: int, taps, mesh=None) -> dict:
    """``cca_path`` as the ``trace`` span's notes, with the kernels'
    rows a block and the rows a row reads before itself where it is
    ``pallas``."""
    path = cca_path(shape, n_head, n_kv_head, taps, mesh)
    if path != "pallas":
        return {"cca_path": path}
    return {"cca_path": path,
            "cca_rows_per_block": cca_mix.block_rows(shape[1]),
            "cca_halo_rows": cca_mix.halo_rows(taps)}


def shift_rows(x, by: int = 1):
    """``x[:, t - by]`` at row ``t`` of ``[B, T, ...]``, zeros before
    the first row: the causal shift."""
    if by == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (by, 0)
    return jnp.pad(x[:, :x.shape[1] - by], pad)


def depthwise_causal_conv(x, w, b):
    """``y_t = sum_j w[j] * x_{t - (K-1-j)} + b`` a channel at a time:
    x [B, T, C], w [K, C], b [C]. The last tap is the token's own."""
    k = w.shape[0]
    xf = x.astype(jnp.float32)
    y = sum(w[j].astype(jnp.float32) * shift_rows(xf, k - 1 - j)
            for j in range(k))
    return (y + b.astype(jnp.float32)).astype(x.dtype)


def grouped_causal_conv(x, w, b):
    """A causal convolution over time whose channels mix within their
    group: x [B, T, G*D], w [K, G, D, D] (tap, group, in, out), b
    [G*D]. One batched matmul a tap."""
    k, g, d, _ = w.shape
    xg = x.reshape(*x.shape[:2], g, d)
    y = sum(jnp.einsum("btgc,gcd->btgd", shift_rows(xg, k - 1 - j),
                       w[j].astype(x.dtype),
                       preferred_element_type=jnp.float32)
            for j in range(k))
    return (y.reshape(x.shape) + b.astype(jnp.float32)).astype(x.dtype)


def qk_mean(q, k):
    """(``(q + rep(k)) / 2``, ``(groupmean(q) + k) / 2``) in float32:
    q [B, T, G, R, D] (R query heads a group), k [B, T, G, D]."""
    q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    return (q + k[:, :, :, None]) * 0.5, (q.mean(axis=3) + k) * 0.5


def l2_normalise(x, eps: float = 1e-6):
    """``sqrt(D) x / |x|`` over the last axis (``|x|^2 + eps`` under
    the root), float32 in and out."""
    d = x.shape[-1]
    return x * (math.sqrt(d) * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + eps))


def partial_rope(x, angles, out_dtype):
    """RoPE in halves (``models/llama.py::apply_rope_half``'s pairing:
    lane i with lane i + rotary/2) on the first ``2 * angles.shape[-1]``
    lanes of each head of x [B, T, H, D], the other lanes as they are;
    float32 inside, ``out_dtype`` out."""
    half = angles.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    cos = jnp.cos(angles)[None, :x.shape[1], None, :]
    sin = jnp.sin(angles)[None, :x.shape[1], None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
        axis=-1).astype(out_dtype)


def _qk_for_kernel(qk, conv0, conv1, tau, angles, n_head, n_kv_head):
    """``conv``, ``mix`` and ``rope``: the compressed ``[q~ | k~]`` to
    (q [B, T, H, D], k [B, T, G, D]) as the kernel reads them."""
    b, t, _ = qk.shape
    h, g = n_head, n_kv_head
    d = qk.shape[-1] // (h + g)
    with jax.named_scope("conv"):
        c = grouped_causal_conv(depthwise_causal_conv(qk, *conv0), *conv1)
    with jax.named_scope("mix"):
        def parts(z):
            return (z[..., :h * d].reshape(b, t, g, h // g, d),
                    z[..., h * d:].reshape(b, t, g, d))
        (q0, k0), (cq, ck) = parts(qk), parts(c)
        m_q, m_k = qk_mean(q0, k0)
        q = l2_normalise(cq.astype(jnp.float32) + m_q)
        k = (l2_normalise(ck.astype(jnp.float32) + m_k)
             * tau.astype(jnp.float32)[:, None])
    with jax.named_scope("rope"):
        return (partial_rope(q.reshape(b, t, h, d), angles, qk.dtype),
                partial_rope(k, angles, qk.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def mixed_qk(qk, conv0, conv1, tau, angles, n_head, n_kv_head):
    """``_qk_for_kernel`` keeping only its inputs for the backward
    pass, which runs it again: between ``[q~ | k~]`` (2.5 kB a token)
    and the kernel's q and k lie a dozen float32 arrays of 4 kB a token
    each, cheaper to make again than to keep (0.5 GB a layer at 16,384
    tokens; PERF.md section 6, PR 38)."""
    return _qk_for_kernel(qk, conv0, conv1, tau, angles, n_head, n_kv_head)


def _mixed_qk_fwd(qk, conv0, conv1, tau, angles, n_head, n_kv_head):
    return (_qk_for_kernel(qk, conv0, conv1, tau, angles, n_head, n_kv_head),
            (qk, conv0, conv1, tau, angles))


def _mixed_qk_bwd(n_head, n_kv_head, saved, g):
    # the barrier keeps XLA from merging this second run with the
    # forward's identical operations, which would keep them after all
    (qk, conv0, conv1, tau, angles), g = jax.lax.optimization_barrier(
        (saved, g))
    pull = jax.vjp(lambda *a: _qk_for_kernel(*a, angles, n_head, n_kv_head),
                   qk, conv0, conv1, tau)[1]
    return (*pull(g), None)


mixed_qk.defvjp(_mixed_qk_fwd, _mixed_qk_bwd)


def cca_attention(qk, v, conv0, conv1, tau, angles, *, n_head: int,
                  n_kv_head: int, attn_fn, mesh=None):
    """Everything between CCA's projections and its output projection.

    qk:     [B, T, (H + G) * D]  the compressed queries, then the keys
    v:      [B, T, G * D]        the values, the shifted half in place
    conv0:  (w [K0, (H+G)*D], b)         the depthwise convolution
    conv1:  (w [K1, H+G, D, D], b)       the convolution within heads
    tau:    [G] float32                  the keys' temperature
    angles: [T, rotary / 2]              ``models/llama.py::rope_freqs``
    mesh:   the devices the program spans, for ``cca_path``
    Returns o [B, T, H * D] in ``qk``'s dtype."""
    b, t, _ = qk.shape
    rep = n_head // n_kv_head
    taps = (conv0[0].shape[0], conv1[0].shape[0])
    if cca_path(qk.shape, n_head, n_kv_head, taps, mesh) == "pallas":
        q, k = cca_mix.cca_mix(
            qk, conv0, conv1, tau, angles, n_head=n_head,
            n_kv_head=n_kv_head, mesh=mesh,
            batch_axes=program.batch_axes(mesh, b))
    else:
        q, k = mixed_qk(qk, conv0, conv1, tau, angles, n_head, n_kv_head)
    with jax.named_scope("core"):
        # the kernels take equal head counts (models/nemotron_h.py:273)
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v.reshape(b, t, n_kv_head, -1), rep, axis=2)
        return attn_fn(q, k, v).reshape(b, t, -1)

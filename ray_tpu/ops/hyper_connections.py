"""Manifold-constrained hyper-connections (mHC): a residual path of
``n`` streams mixed by learned, Sinkhorn-normalised maps.

Hyper-connections (arXiv:2409.19606) widen a block's residual stream to
``n`` streams that learned maps read from, write to and mix; mHC
(arXiv:2512.24880) holds the mixing map on the doubly stochastic
matrices by a fixed number of Sinkhorn normalisations, so that depth
neither grows nor shrinks the streams' sum. A token's state is ``X`` in
``R^{n x d}``; ``X_0`` is ``n`` copies of the embedding (``hc_expand``).
Each sub-layer ``F`` (attention with its norm, the MLP with its norm)
has maps of its own: ``phi`` in ``R^{nd x (n^2 + 2n)}``, ``b`` in
``R^{n^2 + 2n}`` and three gates ``alpha = (pre, post, res)``. With
``x = vec(X)`` (``hc_maps``, float32):

    m      = (x / sqrt(mean(x^2) + 1e-6)) phi              [n^2 + 2n]
    H_pre  = sigmoid(alpha_pre m[0:n] + b[0:n])            [n]
    H_post = 2 sigmoid(alpha_post m[n:2n] + b[n:2n])       [n]
    A      = clip(alpha_res mat(m[2n:]) + mat(b[2n:]), -clamp, clamp)
    M      = exp(A); ``iters`` times: M <- M / (colsum(M) + eps),
             M <- M / (rowsum(M) + eps);  H_res = M        [n, n]

(``mat`` fills rows first: entry ``n i + j`` is ``A[i, j]``; ``colsum``
sums over ``i``, ``rowsum`` over ``j``), and the sub-layer is

    u     = sum_i H_pre[i] X[i]                            ``hc_pre``
    y     = F(RMSNorm(u))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y           ``hc_post``

After the last block ``x_L = sum_i X[i]`` (``hc_collapse``). With one
stream and all three maps 1 this is ``x + F(norm(x))``.

**Layout.** The state is ``[B, T, n d]``, stream ``i`` the lanes ``[i d,
(i + 1) d)``, in the model's compute type; it is never held with ``n``
second-minor (4 sublanes pad to 8 or 16 in an HBM tile, which would
multiply the state's bytes). The maps are float32 with the **tokens in
the lanes**: ``H_pre`` and ``H_post`` ``[n, B, T]``, ``H_res`` ``[n, n,
B, T]``, 16 floats a token through the Sinkhorn loop and its backward.
The one matmul, ``[T, n d] x [n d, n^2 + 2n]``, takes the state in its
own type and ``phi`` rounded to it and accumulates in float32 (on the
TPU a float32 product at the default precision rounds both operands to
bfloat16 too; the state already is); the norm's factor, a float32
reduction over the ``n d`` lanes, multiplies the product after it
(``(x r) phi = r (x phi)``), so no float32 copy of the state is made.

**Who runs what.** ``pre`` and ``post`` are plain ``jax.numpy`` that XLA
fuses into one pass each over the state, forward, and are at their
bytes. The maps have two paths, which ``hc_maps_path`` chooses between
from what it can see (the backend, the shapes, the mesh; no switch):
``pallas``, the kernel pair of ``ops/pallas/hc_maps.py`` (PR 60), where
XLA keeps the norm's reduction and the one product and the chain from
those 25 floats a token to the three maps, the 20 normalisations and
their backward, is one kernel a pass in VMEM; and ``xla``, the function
below as ``jax.numpy``, whose loop XLA runs as ~45 fusions forward and
twice that backward, a few microseconds each (the CPU's path, init
tracing's, odd shapes', and what the tests hold the kernels to). The
kernels' forward rule names the maps and what its backward reads
(``MAPS_KEEPS``), and a recomputed block that keeps those names
(``models/joyai.py``) makes none of them twice. What the whole path
needs of the HBM whatever implements it is ``(6 n + 5) d`` elements a
token a sub-layer, forward and backward (``benchmark/benchlib/
flops_xing.py::hc_train_cost``); a kernel that shares one read of the
state between the maps and ``pre``, or ``post`` with the next
sub-layer's maps, is not here (ROADMAP A12).

**Meshes.** ``dp`` / ``fsdp`` shard the batch and need nothing. ``sp``
and ``tp`` are refused by name (``SPLIT_STATE``): a state whose
``n d`` lanes are split over chips would need the norm's and the
product's partial sums gathered a sub-layer, and a split sequence
nothing, but neither has been run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas import hc_maps as kernels, program


# what a mesh that splits the state would take, for ``program.refuse``
# (``models/joyai.py``, before a layer is built)
SPLIT_STATE = {
    "sp": "the n-stream state with the sequence split over chips",
    "tp": "the n-stream state with its lanes split over chips (the maps' "
          "norm and product would sum over chips a sub-layer)"}


def map_width(n: int) -> int:
    """Columns of ``phi`` and entries of ``b``: ``n^2 + 2n``."""
    return n * n + 2 * n


def streams(x, n: int):
    """The ``n`` streams of a state ``[..., n d]``, each ``[..., d]``."""
    d = x.shape[-1] // n
    return [x[..., i * d:(i + 1) * d] for i in range(n)]


def hc_expand(x, n: int):
    """``X_0``: ``n`` copies of ``x`` [B, T, d] side by side."""
    return jnp.concatenate([x] * n, -1)


def hc_collapse(x, n: int):
    """``sum_i X[i]``, [B, T, d], summed in float32."""
    parts = streams(x, n)
    return sum(p.astype(jnp.float32) for p in parts).astype(x.dtype)


def stream_spread(x, n: int):
    """The RMS of ``X[i] - mean_i X[i]`` over the RMS of ``X``, one
    number for the whole array: 0 where the streams are one."""
    parts = [p.astype(jnp.float32) for p in streams(x, n)]
    mean = sum(parts) / n
    off = sum(jnp.sum((p - mean) ** 2) for p in parts)
    return jnp.sqrt(off / sum(jnp.sum(p * p) for p in parts))


def sinkhorn(a, iters: int, eps: float):
    """``exp(a)`` [n, n, ...] normalised ``iters`` times, columns (the
    sum over axis 0) then rows (over axis 1), ``eps`` in both
    denominators."""
    m = jnp.exp(a)
    for _ in range(iters):
        m = m / (m.sum(0, keepdims=True) + eps)
        m = m / (m.sum(1, keepdims=True) + eps)
    return m


# The tokens of a grid cell of the kernels.
MAPS_BLOCK_TOKENS = kernels.BLOCK_ROWS * 128


def hc_maps_path(shape, n: int, mesh=None) -> str:
    """Which ``hc_maps`` compiles for a state ``shape`` [B, T, n d]:
    ``pallas`` (the kernels of ``ops/pallas/hc_maps.py``) on a TPU where
    ``T`` is whole 128-lane tiles and ``program.batch_axes`` finds the
    program one the kernels can serve, else ``xla``."""
    if (jax.default_backend() == "tpu" and len(shape) == 3
            and kernels.shapes_ok(shape[1])
            and program.batch_axes(mesh, shape[0]) is not None):
        return "pallas"
    return "xla"


def hc_maps(x, phi, b, alpha, *, n: int, iters: int, eps: float,
            clamp: float, norm_eps: float = 1e-6, mesh=None):
    """``(H_pre [n, B, T], H_post [n, B, T], H_res [n, n, B, T])`` in
    float32 from the state ``x`` [B, T, n d], ``phi`` [n d, n^2 + 2n],
    ``b`` [n^2 + 2n] and ``alpha`` [3] (module docstring). ``mesh`` is
    the mesh the program is sharded over, if the caller knows one:
    ``hc_maps_path`` decides from it between the kernels and the
    ``jax.numpy`` function below."""
    if hc_maps_path(x.shape, n, mesh) == "pallas":
        return kernels.hc_maps(
            x, phi, b, alpha, n=n, iters=iters, eps=eps, clamp=clamp,
            norm_eps=norm_eps, mesh=mesh,
            batch_axes=program.batch_axes(mesh, x.shape[0]))
    return _hc_maps_xla(x, phi, b, alpha, n=n, iters=iters, eps=eps,
                        clamp=clamp, norm_eps=norm_eps)


def _hc_maps_xla(x, phi, b, alpha, *, n, iters, eps, clamp, norm_eps):
    f32 = jnp.float32
    r = jax.lax.rsqrt(jnp.mean(jnp.square(x.astype(f32)), -1) + norm_eps)
    # [B, T, c] from the matmul, then the 24 floats a token turned so
    # that the tokens lie in the lanes: the state itself is not
    m = jnp.moveaxis(jnp.einsum("btk,kc->btc", x, phi.astype(x.dtype),
                                preferred_element_type=f32), -1, 0) * r
    b = b.astype(f32)[:, None, None]
    alpha = alpha.astype(f32)
    h_pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + b[n:2 * n])
    a = jnp.clip(alpha[2] * m[2 * n:] + b[2 * n:], -clamp, clamp)
    h_res = sinkhorn(a.reshape(n, n, *a.shape[1:]), iters, eps)
    return h_pre, h_post, h_res


def hc_pre(x, h_pre):
    """``u = sum_i H_pre[i] X[i]``, [B, T, d] in the state's type."""
    n = h_pre.shape[0]
    u = sum(h_pre[i][..., None] * p.astype(jnp.float32)
            for i, p in enumerate(streams(x, n)))
    return u.astype(x.dtype)


def hc_post(x, y, h_post, h_res):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``, [B, T, n d] in
    the state's type; ``y`` [B, T, d] is the sub-layer's output."""
    n = h_post.shape[0]
    parts = [p.astype(jnp.float32) for p in streams(x, n)]
    y = y.astype(jnp.float32)
    return jnp.concatenate(
        [(sum(h_res[i, j][..., None] * parts[j] for j in range(n))
          + h_post[i][..., None] * y).astype(x.dtype) for i in range(n)], -1)


def res_row_err(h_res):
    """The largest ``|rowsum(H_res) - 1|`` over the tokens: what the
    iterations leave (the last normalisation is the rows', so this is
    of the order of ``eps``)."""
    return jnp.max(jnp.abs(h_res.sum(1) - 1.0))

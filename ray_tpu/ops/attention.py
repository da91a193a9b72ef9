"""Attention ops.

- ``causal_attention``: dense causal attention. On single-device TPU
  with flash-blockable shapes it dispatches to the Pallas flash kernel
  (ops/pallas/flash_attention.py), which reads q, k, v as the
  projections wrote them ([B, T, H, D] is [B, T, H*D] for free; its
  grid runs over the batch and 128-lane blocks of H*D) and writes
  the output projection's operand: no copy on either side. Otherwise
  ``jax.nn.dot_product_attention`` (XLA fused path). It serves q, k and
  v of one shape; the kernels' other shape, latent attention's keys of
  128 + 64 against values of 128 with the rotary key shared by the
  heads, is ``ops/mla.py::latent_attention``'s, which has its own
  dispatch (``mla_path``) to the kernels of the same file. With a
  ``window`` a row sees its last ``window`` keys only (a sliding-window
  layer): the kernel's grids then run over the band's blocks alone.
- ``differential_attention``: two softmax maps a pair of heads,
  subtracted (Phi-4-mini-flash's ``S``, ``F`` and ``X`` layers): the four
  equal-width products ``A1 v1, A1 v2, A2 v1, A2 v2`` as one call of the
  function above over twice the heads, so the flash kernels and their
  window run it; then the ``lambda`` combination and the pair's RMSNorm.
- ``ring_attention``: sequence-parallel causal attention over an ICI
  ring. The reference has NO sequence parallelism in-tree (SURVEY.md
  §5.7); here it is first-class: K/V blocks rotate around the ``sp``
  mesh axis via ``lax.ppermute`` while each device streams blockwise
  softmax over its local queries (log-sum-exp accumulation, the
  RingAttention / blockwise-attention recipe). Designed to run inside
  ``shard_map`` with the sequence dim sharded on ``sp``.

Shapes follow jax convention: [batch, seq, heads, head_dim].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.pallas import program

_NEG_INF = -1e30

def _flash_ok(q, k, v) -> bool:
    """This function's kernel shape: three equal [B, T, H, D]. (Unequal
    widths have a kernel too, reached through ``ops/mla.py``.)"""
    return (q.shape == k.shape == v.shape
            and flash_eligible(q.shape[1], q.shape[-1]))


def flash_eligible(t: int, d: int) -> bool:
    """Would ``causal_attention`` dispatch [*, t, *, d] self-attention
    to the Pallas flash kernel on this backend? Benchmarks use this to
    refuse silently measuring the XLA fallback."""
    from ray_tpu.ops.pallas.flash_attention import (
        flash_attention_shapes_ok,
    )
    return (jax.default_backend() == "tpu"
            and flash_attention_shapes_ok(t, d))


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     scale: float | None = None,
                     force_flash: bool = False,
                     window: int | None = None) -> jax.Array:
    """Causal attention [B, T, H, D] -> [B, T, H, D].

    ``window``: row t sees keys ``t - window < j <= t``, ``window`` of
    them with itself (a sliding-window layer; None: every key up to t).
    Where the kernel is eligible it takes the window and skips the
    blocks below the band through its grids
    (``flash_attention(window=...)``), else XLA's
    ``dot_product_attention(local_window_size=(window - 1, 0))``. The
    paths that split the sequence over chips know no band and refuse a
    window: ``make_sharded_causal_attention`` raises
    ``NotImplementedError`` on a mesh with ``sp > 1`` (ring and Ulysses;
    the halo of keys a chip would need from its neighbour is not
    written).

    Single-device TPU with cleanly-blocking shapes, q, k and v of one
    shape, runs the Pallas flash kernel
    (ops/pallas/flash_attention.py). Operands of unequal shapes (fewer
    key/value heads, keys wider than values) take the XLA path here and
    say so in the trace's notes (``flash_path`` = ``"xla"``,
    ``flash_layout`` = ``"unequal_shapes"``): the kernel for latent
    attention's 192-wide keys and 128-wide values is reached through
    ``ops/mla.py::latent_attention``, not through this function.
    Multi-device
    programs must NOT hit the bare kernel (pallas_call has no SPMD
    partitioning rule): use make_sharded_causal_attention, which
    shard_maps over the mesh and sets ``force_flash`` for the
    per-device local block. Everything else takes the XLA path. A row
    too long for the kernel's backward (``NotImplementedError`` from
    ``flash_attention``) is not sent there: it surfaces.

    Without ``force_flash`` nothing says how many devices the program
    spans, so the process's device count stands in for it: a caller
    that knows its mesh — the models, whenever they are given one —
    goes through make_sharded_causal_attention, which decides from the
    mesh and not from the process.
    """
    if _flash_ok(q, k, v) and (force_flash or jax.device_count() == 1):
        from ray_tpu.ops.pallas.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=True, scale=scale,
                               window=window)
    if not q.shape == k.shape == v.shape:
        from ray_tpu.util import tracing
        tracing.note_trace(flash_path="xla", flash_layout="unequal_shapes")
    if window is None or window >= q.shape[1]:
        return jax.nn.dot_product_attention(q, k, v, scale=scale,
                                            is_causal=True)
    return jax.nn.dot_product_attention(
        q, k, v, scale=scale, is_causal=True,
        local_window_size=(window - 1, 0))


def _to_query_pairs(x, rep: int):
    """Key/value pairs [B, T, G, ...] to the query pairs that read them,
    [B, T, G * rep, ...]: query pair ``j`` reads pair ``j // rep``."""
    return jnp.repeat(x, rep, axis=2)


def differential_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           lam, lam_init: float, subln_scale: jax.Array,
                           *, window: int | None = None, mesh=None,
                           eps: float = 1e-5,
                           scope: str = "core") -> jax.Array:
    """Differential attention (arXiv:2410.05258 as Phi-4-mini-flash
    runs it): q [B, T, 2P, D] is ``P`` pairs of adjacent heads ``(q1_j,
    q2_j)``, k and v [B, T, 2G, D] ``G`` pairs ``(k1_i, k2_i)``, ``(v1_i,
    v2_i)``; query pair ``j`` reads key/value pair ``j // (P // G)``::

        A1 = softmax(q1 k1^T / sqrt(D) + mask)   A2 = softmax(q2 k2^T ..)
        o_j = A1 [v1 | v2] - lam * A2 [v1 | v2]                  (2D wide)
        o_j <- RMSNorm_2D(o_j) * subln_scale * (1 - lam_init)

    ``lam`` is the layer's scalar (float32, traced), ``subln_scale`` its
    one 2D-wide scale; the mask is causal, under ``window`` a row's last
    ``window`` keys. Returns [B, T, P * 2D] in q's dtype, the output
    projection's operand.

    **Four equal-width products, one call**: the operands are written
    out as ``4P`` heads of ``D``, a pair's ``[q1 q1 q2 q2]`` against
    ``[k1 k1 k2 k2]`` and ``[v1 v2 v1 v2]``, so that ``causal_attention``
    (the flash kernels on a TPU, their band under a window) serves
    them and a pair's two results are each one 2D-wide block of its
    output; each score map is then made twice. One call with D-wide keys
    against 2D-wide values would make it once: the flash file has no
    such kernel (ROADMAP B2). The trace's notes say so
    (``attn_products``, ``attn_calls``, ``attn_pairs``). The copies sit
    under the scope ``repeat``, the call under ``scope``, the
    combination and the norm under ``diff``. With a ``mesh`` the call
    goes through ``make_sharded_causal_attention``."""
    from ray_tpu.util import tracing
    b, t, heads, d = q.shape
    pairs, kv_pairs = heads // 2, k.shape[2] // 2
    if heads % 2 or k.shape[2] % 2 or pairs % kv_pairs or k.shape != v.shape:
        raise ValueError(f"differential attention pairs adjacent heads: "
                         f"{heads} query and {k.shape[2]} key/value heads")
    tracing.note_trace(attn_pairs=[pairs, kv_pairs], attn_products=4,
                       attn_calls=1)
    attend = (functools.partial(causal_attention, window=window)
              if mesh is None
              else make_sharded_causal_attention(mesh, window=window))
    rep = pairs // kv_pairs
    with jax.named_scope("repeat"):
        def kv(x, twice):       # [B, T, 2G, D] -> [B, T, 4P, D]
            x = twice(x.reshape(b, t, kv_pairs, 2, d))
            return _to_query_pairs(x, rep).reshape(b, t, 4 * pairs, d)
        q4 = jnp.repeat(q, 2, axis=2)                       # q1 q1 q2 q2
        k4 = kv(k, lambda x: jnp.repeat(x, 2, axis=3))      # k1 k1 k2 k2
        v4 = kv(v, lambda x: jnp.concatenate([x, x], 3))    # v1 v2 v1 v2
    with jax.named_scope(scope):
        out = attend(q4, k4, v4)
    with jax.named_scope("diff"):
        out = out.reshape(b, t, pairs, 2, 2 * d).astype(jnp.float32)
        o = out[..., 0, :] - lam * out[..., 1, :]
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        o = o * (subln_scale.astype(jnp.float32) * (1.0 - lam_init))
        return o.astype(q.dtype).reshape(b, t, pairs * 2 * d)


def _block_attend(q, k, v, acc, row_max, row_sum, mask_mode, scale):
    """One blockwise-attention step with streaming softmax.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]
    acc: [B, Tq, H, D] running numerator
    row_max/row_sum: [B, Tq, H] running logsumexp state
    mask_mode: 0 = full block visible, 1 = causal within block,
               2 = fully masked (skip)
    """
    # scores: [B, H, Tq, Tk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    tq, tk = q.shape[1], k.shape[1]
    causal = jnp.tril(jnp.ones((tq, tk), dtype=bool))
    mask = jnp.where(
        mask_mode == 1, causal[None, None],
        jnp.full((1, 1, tq, tk), mask_mode == 0))
    scores = jnp.where(mask, scores, _NEG_INF)

    block_max = jnp.max(scores, axis=-1)               # [B, H, Tq]
    new_max = jnp.maximum(row_max, block_max.transpose(0, 2, 1))
    correction = jnp.exp(row_max - new_max)            # [B, Tq, H]
    p = jnp.exp(scores - new_max.transpose(0, 2, 1)[:, :, :, None])
    p = jnp.where(mask, p, 0.0)                        # kill -inf rows
    block_sum = p.sum(axis=-1).transpose(0, 2, 1)      # [B, Tq, H]
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype))
    acc = acc * correction[..., None] + pv
    row_sum = row_sum * correction + block_sum
    return acc, new_max, row_sum


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "sp",
                   scale: float | None = None) -> jax.Array:
    """Causal ring attention; call inside shard_map with seq sharded on
    ``axis_name``. Each of the S ring steps overlaps compute of the
    current K/V block with the ICI rotation of the next (XLA schedules
    the ppermute async against the einsums).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    sp = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)

    b, tq, h, d = q.shape
    qf = q.astype(jnp.float32)
    acc0 = jnp.zeros((b, tq, h, d), jnp.float32)
    max0 = jnp.full((b, tq, h), _NEG_INF, jnp.float32)
    sum0 = jnp.zeros((b, tq, h), jnp.float32)

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(i, carry):
        acc, row_max, row_sum, kb, vb = carry
        # K/V block currently held arrived from device (my_idx - i).
        src = (my_idx - i) % sp
        # Causal across blocks: src < me -> fully visible; src == me ->
        # causal inside; src > me -> masked out.
        mask_mode = jnp.where(src == my_idx, 1,
                              jnp.where(src < my_idx, 0, 2))
        acc, row_max, row_sum = _block_attend(
            qf, kb.astype(jnp.float32), vb.astype(jnp.float32),
            acc, row_max, row_sum, mask_mode, scale)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return acc, row_max, row_sum, kb, vb

    acc, row_max, row_sum, _, _ = lax.fori_loop(
        0, sp, step, (acc0, max0, sum0, k, v))
    out = acc / jnp.maximum(row_sum, 1e-30)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = "sp",
                      scale: float | None = None) -> jax.Array:
    """DeepSpeed-Ulysses-style sequence parallelism: all-to-all swaps
    the sharded dimension from sequence to heads, each device runs
    FULL-sequence attention on its head subset (flash-eligible), and
    a second all-to-all swaps back. Call inside shard_map with the
    sequence dim sharded on ``axis_name``; requires
    num_heads % axis_size == 0.

    vs ring attention: ulysses moves activations twice (2 all-to-alls,
    O(B·T·H·D/sp) each) but runs ONE dense/flash kernel over the full
    sequence; ring keeps activations put and rotates K/V around the
    ICI ring in S steps. Ulysses wins when heads divide evenly and the
    per-step latency of S rotations dominates; ring wins at very long
    sequences where full-seq attention per device would not fit.
    """
    sp = lax.psum(1, axis_name)
    # [B, Tl, H, D] -> [B, Tl*sp, H/sp, D]: scatter heads, gather seq
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    out = causal_attention(qh, kh, vh, scale=scale)
    # inverse swap: scatter seq back, gather heads
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def make_sharded_causal_attention(mesh, seq_axis="sp", head_axis="tp",
                                  impl="auto", window: int | None = None,
                                  scale=None):
    """Build an attention fn for activations sharded
    [batch->dp/fsdp, seq->sp, heads->tp]: shard_map-wrapped ring
    attention when the mesh has a real sp axis, dense attention
    otherwise. ``impl`` forces a path: "dense" cannot run on a real sp
    axis (each device only holds a slice of K/V) and raises rather than
    silently running ring. ``scale`` and ``window`` (both
    ``causal_attention``'s) go to each chip's own call; a window with
    ``sp > 1`` raises ``NotImplementedError``: ring and Ulysses know no
    band."""
    from jax.sharding import PartitionSpec as P

    if impl not in ("auto", "dense", "ring", "ulysses"):
        raise ValueError(f"unknown attn impl {impl!r}; "
                         "expected 'auto', 'dense', 'ring' or "
                         "'ulysses'")
    sp = mesh.shape.get(seq_axis, 1)
    if window is not None and sp > 1:
        raise NotImplementedError(
            f"a window of {window} keys on a mesh with {seq_axis}={sp}: "
            "ring and Ulysses attention compute the whole causal "
            "triangle, and a chip's first rows would need the last "
            f"{window - 1} keys of its neighbour (a halo), which is not "
            "written. dp, fsdp and tp shard the batch and the heads and "
            "need nothing.")
    if impl == "dense" and sp > 1:
        raise ValueError(
            f"attn_impl='dense' cannot run on a mesh with "
            f"{seq_axis}={sp}: activations are sequence-sharded, so "
            f"attention must be 'ring' (or 'auto') — or build the "
            f"mesh without a {seq_axis} axis")
    if impl in ("ring", "ulysses") and sp <= 1:
        raise ValueError(
            f"attn_impl={impl!r} requires a real {seq_axis} mesh axis "
            f"(got {seq_axis}={sp}); the O(seq/sp) per-device K/V "
            f"memory you asked for does not exist on this mesh — use "
            f"'auto' or add a {seq_axis} axis")
    # the batch's axes are the ones ``program.batch_axes`` maps over
    batch = tuple(a for a in program.BATCH_AXES if mesh.shape.get(a, 1) > 1)
    if sp <= 1:
        heads = (head_axis if mesh.shape.get(head_axis, 1) > 1
                 else None)
        if mesh.size == 1:
            # A one-device mesh is a one-device program whatever else
            # the process can see (one chip of a four-chip host): the
            # kernel runs bare, with no device-count guard.
            return functools.partial(causal_attention, force_flash=True,
                                     window=window, scale=scale)
        if not batch and heads is None:
            # Attention operands replicated over a multi-device mesh
            # (pp- or ep-only): no axis to shard_map over, and the
            # bare kernel has no SPMD rule, so this is the XLA path.
            def dense(q, k, v):
                return causal_attention(q, k, v, scale, window=window)
            return dense
        # Batch/head-sharded, sequence-replicated: shard_map so each
        # device runs the local block — this is what lets the Pallas
        # flash kernel (no SPMD rule of its own) serve the multi-chip
        # dense path.
        # The shards cross the boundary with heads merged, [B, T, H*D]
        # (a block of heads is a block of the merged dimension): a
        # [.., H, 64] array that has to exist there is half padding in
        # the chip's 128-lane tiles, so XLA lays it out with T
        # innermost and copies it into and out of the kernel's layout.
        spec = P(batch if batch else None, None, heads)
        n_batch = math.prod(mesh.shape[a] for a in batch)
        n_heads = mesh.shape[head_axis] if heads else 1

        def dispatch(q, k, v):
            # Shapes that don't divide the mesh (e.g. the tiny batch
            # used by init tracing) take the plain XLA path.
            if q.shape[0] % n_batch or q.shape[2] % n_heads:
                return causal_attention(q, k, v, scale, window=window)
            d = q.shape[-1]

            def local(*qkv):
                q, k, v = (x.reshape(*x.shape[:2], -1, d) for x in qkv)
                return causal_attention(
                    q, k, v, scale, force_flash=True,
                    window=window).reshape(qkv[0].shape)

            merged = jax.shard_map(local, mesh=mesh,
                                   in_specs=(spec, spec, spec),
                                   out_specs=spec, check_vma=False)
            return merged(*(x.reshape(*x.shape[:2], -1)
                            for x in (q, k, v))).reshape(q.shape)
        return dispatch

    spec = P(batch if batch else None, seq_axis,
             head_axis if mesh.shape.get(head_axis, 1) > 1 else None,
             None)
    local_impl = (ulysses_attention if impl == "ulysses"
                  else ring_attention)
    fn = functools.partial(local_impl, axis_name=seq_axis, scale=scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)

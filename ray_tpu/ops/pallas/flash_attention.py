"""Causal flash attention as a Pallas TPU kernel (fwd + bwd).

The streaming-softmax recipe: the [T, T] score matrix is never
materialized in HBM; each q-block program walks k-blocks keeping a
running (max, sum, accumulator) in VMEM scratch, and the backward pass
recomputes probabilities from the saved log-sum-exp instead of storing
them. MXU-friendly: all matmuls are block-sized with fp32
accumulation (``preferred_element_type``); bf16 inputs stay bf16 into
the MXU.

The reference framework has no attention kernels at all (it hosts
frameworks that bring their own); this is part of the TPU-native
compute path (SURVEY.md §5.7). API shape follows jax convention
[batch, seq, heads, head_dim].

Layout: the kernels index the projections' own [B, T, H*D] (the
[B, T, H, D] arguments reshaped, which moves nothing): a block is
(1, rows, 128) lanes of the last dimension — two heads side by side at
D=64, walked as two static 64-lane halves in the body, or one head
where D is a multiple of 128 — and the output is written the same way,
as the output projection's operand. Nothing is transposed, padded or
copied between the projections' matmuls and the custom calls, in
either pass. A shape that cannot be blocked on whole 128-lane tiles
(an odd head count at D=64, another head width) is folded to
[B*H, T, D] first, one head a block, at a transpose each way; the same
kernels run it. ``flash_attention`` leaves which one ran in the trace's
notes (``flash_layout`` = "bthd" | "folded").

Grid (both passes): (batch, lane blocks) where a whole row fits one
block (T <= 1024), else (batch, lane blocks, outer_block, inner_block)
with the innermost grid dimension "arbitrary" (sequential on TPU), so
VMEM scratch carries state across inner steps of one outer block.
Folded, "batch" is batch*heads and there is one lane block.

Set-up: the two functions that hold the pallas_calls are jitted, so a
model's layers, which call them at one shape, trace each kernel and
lower it to Mosaic once a trace of the step, not once a layer.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.util import tracing

_NEG_INF = -1e30


def flash_attention_available() -> bool:
    return jax.default_backend() == "tpu"


def _pick_block(t: int, target: int = 1024) -> int:
    """Largest divisor of t that is <= target and a multiple of 8.

    The rows of a block: its lanes are chosen from the head width
    (``_heads_per_block``), and a grid cell is one (batch row, lane
    block, q-block, k-block). Default target 1024: on v5e-class chips
    the per-grid-cell overhead (pipeline fill, scratch init, mask/exp
    VPU work) dominates below ~1k blocks — measured 16.5ms vs 21.2ms
    attention time per GPT-2 step for 1024x1024 vs 512x512 blocks,
    even though the single-block causal path computes the full (not
    triangular) score matrix."""
    best = 0
    for b in range(8, min(t, target) + 1, 8):
        if t % b == 0:
            best = b
    return best


def _masked_scores(q, k, iq, ik, *, scale, bq, bk, causal):
    """Scaled q·kᵀ for one (q-block, k-block) pair with the causal
    mask applied in absolute coordinates — shared by the fwd and both
    bwd kernels so the mask can never diverge between passes."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [bq, bk]
    if causal:
        row0, col0 = iq * bq, ik * bk
        rows = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        cols = col0 + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    return s


# ---------------------------------------------------------------------------
# the kernels' view of their operands
# ---------------------------------------------------------------------------
#
# Every kernel below takes q, k, v (and do, o) as [N, T, G*L]: N rows
# of the grid's first dimension, G blocks of L lanes along the last.
# A block holds ``hpb`` heads of width ``d`` side by side (L = hpb*d),
# which the body walks as static lane slices. ``lse`` (and ``delta``)
# are [N, G, T/bq, hpb, bq] float32, a q-block's rows along the lanes:
# a [bq, 1] column, the shape the scores broadcast against, fills one
# lane in 128 of the chip's tiles (201 MB a GPT-2 layer where this is
# 1.6), so the body turns columns into rows on the way out and back
# on the way in.
#
#   direct ("bthd"):  N = B,   G*L = H*D, L = 128 lanes (two heads at
#                     D=64) or D (one head, D a multiple of 128) — the
#                     projections' own [B, T, H*D], reshaped for free
#   folded:           N = B*H, G = 1, L = D, hpb = 1 — [B*H, T, D],
#                     which costs a transpose each way

def _head_slices(d, hpb):
    return [slice(j * d, (j + 1) * d) for j in range(hpb)]


def _seq_spec(rows, lanes, index_map):
    return pl.BlockSpec((1, rows, lanes), index_map)


def _stat_spec(hpb, rows, index_map):
    return pl.BlockSpec((None, None, None, hpb, rows), index_map)


def _as_row(col):
    """[r, 1] -> [1, r], as a transpose of whole 128-lane tiles."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1]


def _as_col(row):
    """[1, r] -> [r, 1]."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _delta(o_ref, do_ref, sl):
    """The row sums of o * do for the head in lanes ``sl``: [rows, 1],
    made in the kernel from blocks it holds, never an array in HBM."""
    return jnp.sum(o_ref[0, :, sl].astype(jnp.float32)
                   * do_ref[0, :, sl].astype(jnp.float32),
                   axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, bq, bk, nk, d, hpb,
                causal):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: skip blocks strictly above the diagonal.
    diag_ok = (not causal) or (ik * bk <= iq * bq + bq - 1)

    @pl.when(diag_ok)
    def _attend():
        for j, sl in enumerate(_head_slices(d, hpb)):
            q = q_ref[0, :, sl]                # [bq, d]
            k = k_ref[0, :, sl]                # [bk, d]
            v = v_ref[0, :, sl]
            s = _masked_scores(q, k, iq, ik, scale=scale, bq=bq,
                               bk=bk, causal=causal)

            m_prev = m_ref[j]                  # [bq, 128] (replicated)
            block_max = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
            m_new = jnp.maximum(m_prev, jnp.broadcast_to(
                block_max, m_prev.shape))
            corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])    # [bq, 1]
            p = jnp.exp(s - m_new[:, :1])                   # [bq, bk]
            l_ref[j] = l_ref[j] * corr + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), m_prev.shape)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [bq, d]
            acc_ref[:, sl] = acc_ref[:, sl] * corr + pv
            m_ref[j] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        for j, sl in enumerate(_head_slices(d, hpb)):
            l = l_ref[j][:, :1]
            o_ref[0, :, sl] = (acc_ref[:, sl] / jnp.maximum(
                l, 1e-30)).astype(o_ref.dtype)
            lse_ref[j:j + 1] = _as_row(m_ref[j][:, :1] + jnp.log(
                jnp.maximum(l, 1e-30))).astype(lse_ref.dtype)


def _fwd_single_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                       *, scale, t, d, hpb, causal):
    """Single-block forward: the whole row fits one block, so plain
    (one-pass) softmax replaces the streaming max/sum scratch state —
    fewer VPU ops and no cross-iteration scratch."""
    for j, sl in enumerate(_head_slices(d, hpb)):
        q = q_ref[0, :, sl]
        k = k_ref[0, :, sl]
        v = v_ref[0, :, sl]
        s = _masked_scores(q, k, 0, 0, scale=scale, bq=t, bk=t,
                           causal=causal)
        m = jnp.max(s, axis=-1, keepdims=True)             # [t, 1]
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, :, sl] = (o / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)
        lse_ref[j:j + 1] = _as_row(
            m + jnp.log(jnp.maximum(l, 1e-30))).astype(lse_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "bq", "bk", "d", "hpb", "interpret"))
def _flash_fwd(q, k, v, *, scale, causal, bq, bk, d, hpb, interpret):
    """(out [N, T, G*L], lse [N, G, T/bq, hpb, bq]). Under ``jax.jit`` so
    that a model's layers, which call it at one shape, trace and lower
    it once a trace of the step and share one ``func.func``: XLA
    inlines the calls again, each under its caller's scope."""
    n, t, w = q.shape
    lanes = d * hpb
    g = w // lanes
    nq, nk = t // bq, t // bk
    out_shape = [jax.ShapeDtypeStruct((n, t, w), q.dtype),
                 jax.ShapeDtypeStruct((n, g, nq, hpb, bq), jnp.float32)]
    if nq == 1 and nk == 1:
        seq = _seq_spec(t, lanes, lambda b, c: (b, 0, c))
        return pl.pallas_call(
            functools.partial(_fwd_single_kernel, scale=scale, t=t,
                              d=d, hpb=hpb, causal=causal),
            grid=(n, g),
            in_specs=[seq, seq, seq],
            out_specs=[seq,
                       _stat_spec(hpb, t, lambda b, c: (b, c, 0, 0, 0))],
            out_shape=out_shape,
            interpret=interpret,
        )(q, k, v)
    q_spec = _seq_spec(bq, lanes, lambda b, c, i, j: (b, i, c))
    kv_spec = _seq_spec(bk, lanes, lambda b, c, i, j: (b, j, c))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk,
                          nk=nk, d=d, hpb=hpb, causal=causal),
        grid=(n, g, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   _stat_spec(hpb, bq, lambda b, c, i, j: (b, c, i, 0, 0))],
        out_shape=out_shape,
        scratch_shapes=[
            _vmem((bq, lanes)),      # acc
            _vmem((hpb, bq, 128)),   # running max (replicated lanes)
            _vmem((hpb, bq, 128)),   # running sum (replicated lanes)
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v)


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   dq_ref, delta_ref, acc_ref, *, scale, bq, bk, nk, d,
                   hpb, causal):
    """dq of one q-block, and its ``delta`` (the row sums of o * do,
    made once where the block's o and do are at hand) for this kernel's
    k-steps and for the dk/dv kernel after it."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for j, sl in enumerate(_head_slices(d, hpb)):
            delta_ref[j:j + 1] = _as_row(_delta(o_ref, do_ref, sl))

    diag_ok = (not causal) or (ik * bk <= iq * bq + bq - 1)

    @pl.when(diag_ok)
    def _step():
        for j, sl in enumerate(_head_slices(d, hpb)):
            q = q_ref[0, :, sl]
            k = k_ref[0, :, sl]
            v = v_ref[0, :, sl]
            # bf16 operands into the MXU (f32 operands run it at a
            # fraction of peak); accumulation stays f32.
            do = do_ref[0, :, sl]
            lse = _as_col(lse_ref[j:j + 1])      # [bq, 1]
            delta = _as_col(delta_ref[j:j + 1])  # [bq, 1]
            s = _masked_scores(q, k, iq, ik, scale=scale, bq=bq,
                               bk=bk, causal=causal)
            p = jnp.exp(s - lse)                            # [bq, bk]
            dov = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [bq, bk]
            ds = p * (dov - delta) * scale
            acc_ref[:, sl] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, bq, bk, nq, d, hpb, causal):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    diag_ok = (not causal) or (ik * bk <= iq * bq + bq - 1)

    @pl.when(diag_ok)
    def _step():
        for j, sl in enumerate(_head_slices(d, hpb)):
            q = q_ref[0, :, sl]
            k = k_ref[0, :, sl]
            v = v_ref[0, :, sl]
            do = do_ref[0, :, sl]              # bf16 operand for the MXU
            lse = _as_col(lse_ref[j:j + 1])      # [bq, 1]
            delta = _as_col(delta_ref[j:j + 1])  # [bq, 1]
            s = _masked_scores(q, k, iq, ik, scale=scale, bq=bq,
                               bk=bk, causal=causal)
            p = jnp.exp(s - lse)                            # [bq, bk]
            dv_acc[:, sl] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [bk, d]
            dov = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dov - delta) * scale                  # [bq, bk]
            dk_acc[:, sl] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [bk, d]

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, *, scale, t, d, hpb,
                      causal):
    """Single-block backward (t fits one block): computes the score
    matrix ONCE for dq, dk, AND dv — the two-pass kernels each
    recompute s/p/dov, so this saves a full [t,t] matmul + exp pass.
    No cross-block accumulation, so no scratch is needed."""
    for j, sl in enumerate(_head_slices(d, hpb)):
        q = q_ref[0, :, sl]
        k = k_ref[0, :, sl]
        v = v_ref[0, :, sl]
        do = do_ref[0, :, sl]                  # bf16 operand for the MXU
        lse = _as_col(lse_ref[j:j + 1])          # [t, 1]
        delta = _delta(o_ref, do_ref, sl)      # [t, 1]
        s = _masked_scores(q, k, 0, 0, scale=scale, bq=t, bk=t,
                           causal=causal)
        p = jnp.exp(s - lse)                               # [t, t]
        pb = p.astype(do.dtype)
        dv_ref[0, :, sl] = jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)
        dov = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [t, t]
        ds = (p * (dov - delta) * scale).astype(q.dtype)
        dq_ref[0, :, sl] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)
        dk_ref[0, :, sl] = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dk_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "bq", "bk", "d", "hpb", "interpret"))
def _flash_bwd(q, k, v, out, lse, g, *, scale, causal, bq, bk, d, hpb,
               interpret):
    """(dq, dk, dv), each [N, T, G*L]; jitted for the reason
    ``_flash_fwd`` is."""
    n, t, w = q.shape
    lanes = d * hpb
    ng = w // lanes
    nq, nk = t // bq, t // bk
    do = g.astype(q.dtype)
    grads = [jax.ShapeDtypeStruct((n, t, w), x.dtype) for x in (q, k, v)]
    if nq == 1 and nk == 1:
        seq = _seq_spec(t, lanes, lambda b, c: (b, 0, c))
        return pl.pallas_call(
            functools.partial(_bwd_fused_kernel, scale=scale, t=t,
                              d=d, hpb=hpb, causal=causal),
            grid=(n, ng),
            in_specs=[seq, seq, seq, seq, seq,
                      _stat_spec(hpb, t, lambda b, c: (b, c, 0, 0, 0))],
            out_specs=[seq, seq, seq],
            out_shape=grads,
            interpret=interpret,
        )(q, k, v, out, do, lse)

    q_spec = _seq_spec(bq, lanes, lambda b, c, i, j: (b, i, c))
    kv_spec = _seq_spec(bk, lanes, lambda b, c, i, j: (b, j, c))
    stat = _stat_spec(hpb, bq, lambda b, c, i, j: (b, c, i, 0, 0))
    dq, delta = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, bq=bq, bk=bk,
                          nk=nk, d=d, hpb=hpb, causal=causal),
        grid=(n, ng, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, stat],
        out_specs=[q_spec, stat],
        out_shape=[grads[0], jax.ShapeDtypeStruct(lse.shape, lse.dtype)],
        scratch_shapes=[_vmem((bq, lanes))],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v, out, do, lse)

    q_spec = _seq_spec(bq, lanes, lambda b, c, j, i: (b, i, c))
    kv_spec = _seq_spec(bk, lanes, lambda b, c, j, i: (b, j, c))
    stat = _stat_spec(hpb, bq, lambda b, c, j, i: (b, c, i, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, bq=bq, bk=bk,
                          nq=nq, d=d, hpb=hpb, causal=causal),
        grid=(n, ng, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat, stat],
        out_specs=[kv_spec, kv_spec],
        out_shape=grads[1:],
        scratch_shapes=[_vmem((bk, lanes)), _vmem((bk, lanes))],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

class _Static(NamedTuple):
    """What the kernels are specialised on, besides their shapes."""
    scale: float
    causal: bool
    bq: int
    bk: int
    d: int          # head width
    hpb: int        # heads in a lane block
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_core(q, k, v, static: _Static):
    return _flash_fwd(q, k, v, **static._asdict())[0]


def _flash_core_fwd(q, k, v, static):
    out, lse = _flash_fwd(q, k, v, **static._asdict())
    return out, (q, k, v, out, lse)


def _flash_core_bwd(static, res, g):
    return _flash_bwd(*res, g, **static._asdict())


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _heads_per_block(h: int, d: int) -> int:
    """Heads in one lane block of the projections' [B, T, H*D], or 0
    where that layout cannot be blocked on whole 128-lane tiles and
    the fold to [B*H, T, D] serves: one head when D is a multiple of
    128, two side by side at D=64 when they pair up."""
    if d % 128 == 0:
        return 1
    if d == 64 and h % 2 == 0:
        return 2
    return 0


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *, causal: bool = True,
                    scale: float | None = None,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """Flash attention on [B, T, H, D]; differentiable (custom VJP).

    Falls back to the caller's dense path when shapes don't block
    cleanly — check with ``flash_attention_shapes_ok`` or catch
    ValueError.

    The kernels read q, k, v where the projections wrote them and
    write the output where the output projection reads it: [B, T, H, D]
    is [B, T, H*D] for free, and a block is 128 lanes of it (see
    ``_heads_per_block``). Shapes that cannot be blocked so are folded
    to [B*H, T, D], at a transpose each way; which of the two ran is in
    the trace's notes (``flash_layout``).
    """
    b, t, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    bq = block_q or _pick_block(t)
    bk = block_k or _pick_block(t)
    if bq == 0 or bk == 0 or t % bq or t % bk:
        raise ValueError(
            f"seq len {t} not divisible into flash blocks")
    direct = _heads_per_block(h, d)
    hpb = direct or 1           # folded: one head a block
    # Made here and not in the jitted functions: jax caches their
    # traces, so the step's second trace would find no note.
    tracing.note_trace(
        flash_layout="bthd" if direct else "folded",
        flash_lanes_per_block=d * hpb,
        flash_path="single_block" if bq == t == bk else "multi_block")
    static = _Static(float(scale), causal, bq, bk, d, hpb, interpret)
    if direct:
        out = _flash_core(q.reshape(b, t, h * d), k.reshape(b, t, h * d),
                          v.reshape(b, t, h * d), static)
        return out.reshape(b, t, h, d)

    def fold(x):    # [B, T, H, D] -> [B*H, T, D]
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    out = _flash_core(fold(q), fold(k), fold(v), static)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def flash_attention_shapes_ok(t: int, d: int) -> bool:
    return _pick_block(t) >= 128 and d % 8 == 0


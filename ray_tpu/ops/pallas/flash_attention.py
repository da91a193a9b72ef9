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
[B, T, H, D] arguments reshaped, which moves nothing), 128 lanes of the
last dimension a block, and write the output the same way: nothing is
transposed, padded or copied between the projections' matmuls and the
custom calls, in either pass. A shape that cannot be blocked on whole
128-lane tiles is folded to [B*H, T, D] first, at a transpose each way;
the same kernels run it (the comment above ``_head_slices``; which ran
is in the trace's notes, ``flash_layout`` = "bthd" | "folded").

Grid: (batch, lane blocks) where a whole row fits one block (T <=
1024), in both passes. A longer row's forward is (batch, lane blocks,
q-block, key cell) with the innermost dimension "arbitrary" (sequential
on TPU), so VMEM scratch carries the streaming softmax across the key
blocks of one q-block. Blocks are square: one size ``blk`` each way
(``_window_block``'s, or the tests' ``block``). Folded, "batch" is
batch*heads and there is one lane block.

The backward pass is one kernel a shape, three in the file, and nothing
but the shapes decides which:

- ``_bwd_fused_kernel``, a row of one block (the GPT-2 cells): the
  scores made once for dq, dk and dv, walked as the forward's slabs.
- ``_bwd_kernel``, a row of several blocks at equal widths (OLMoE,
  Nemotron, ZAYA, SmallThinker): grid (batch, lane blocks, key block,
  query cell), the last two in order; a cell makes the scores and
  ``ds`` of its block pair once, five MXU passes, and ``dq`` of the
  lane block's whole row (``[T, 128]`` float32: 8.4 MB at 16,384 rows)
  rides in VMEM scratch across the key blocks.
- ``_mla_bwd_kernel``, latent attention's keys of two parts (JoyAI):
  the same design with five gradients (the file's last section).

The two multi-block kernels hold a whole row's ``dq`` in VMEM
(``flash_bwd_resident_rows`` in the trace's notes), so the row has a
limit: ``_bwd_fits`` (65,536 rows at 128 lanes fit ``_BWD_VMEM``,
131,072 do not) and ``_mla_bwd_fits`` (32,768 fit ``_MLA_BWD_VMEM``,
65,536 do not). A row past it is refused at trace time with a
``NotImplementedError`` that gives the rows, the bytes and the budget;
the ways on are to split the sequence over an ``sp`` mesh axis or to
keep ``dq`` resident over the band's q-rows alone (ROADMAP C4). It is
no ``ValueError``: that sends a caller to the dense path, and a dense
``[T, T]`` at such a length is no fallback.

The causal triangle: the multi-block kernels skip the blocks above the
diagonal through the grid. A single-block body has no grid to skip
with, so it walks its [T, T] block as static row slabs — slab i is
rows [i*S, (i+1)*S) against keys [0, (i+1)*S), slices that fall on
whole tiles — and so computes the triangle too, not the square
(``_causal_slabs``; how many it walked is in the trace's notes,
``flash_causal_slabs``, 1 for the square).

A window (``flash_attention(window=w)``: row t sees keys t - w < j <=
t, the sliding-window layers of a window/global stack) adds the band's
second edge: the multi-block grids' innermost dimension runs over the
band's blocks alone, in both passes (key cells forward, query cells
backward), so the blocks below the band are in no cell, and a window
shorter than a block brings the block down to itself (``_band``, the
comment above ``_keys_of``, ``_window_block``). Without one: no trace.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops.remat import name_core_results
from ray_tpu.util import tracing

_NEG_INF = -1e30


def flash_attention_available() -> bool:
    return jax.default_backend() == "tpu"


def _pick_block(t: int, target: int = 1024) -> int:
    """Largest divisor of t that is <= target and a multiple of 8.

    The rows of a block, queries and keys alike (its lanes come from
    the head width, ``_heads_per_block``), from the row alone: under a
    window shorter than it ``_window_block`` chooses. Target 1024: a
    grid cell has its price (pipeline fill, scratch init, the streaming
    softmax's VPU work), so a row that fits one block takes one, cut
    inside the body by static slices (``_causal_slabs``): 33.1 ms of
    kernels a GPT-2 step so against 46.6 for the square in one piece
    (one v5e chip, 12 layers of 32 x 12 heads; PERF.md 6, PR 31)."""
    best = 0
    for b in range(8, min(t, target) + 1, 8):
        if t % b == 0:
            best = b
    return best


def _masked_scores(q, k, iq, ik, *, scale, blk, causal, window=None):
    """Scaled q·kᵀ for one (q-block, k-block) pair with the causal
    mask applied in absolute coordinates — shared by the forward and the
    backward kernel so the mask can never diverge between passes. Under
    a ``window`` a row sees its last ``window`` keys, itself among them:
    ``row - window < col <= row``."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [blk, blk]
    if causal:
        row0, col0 = iq * blk, ik * blk
        rows = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (blk, blk), 0)
        cols = col0 + jax.lax.broadcasted_iota(
            jnp.int32, (blk, blk), 1)
        seen = rows >= cols
        if window is not None:
            seen &= cols > rows - window
        s = jnp.where(seen, s, _NEG_INF)
    return s


# ---------------------------------------------------------------------------
# the band of a window, in blocks
# ---------------------------------------------------------------------------
#
# Under a window the live blocks of a q-block no longer start at key
# block 0, and the live q-blocks of a key block end: the multi-block
# grids' innermost dimension then runs over the band alone (the most
# cells any outer block needs) and the index maps add the band's first
# block, so the blocks below the band are in no cell, as the blocks
# above the diagonal are in none of the causal grid's live ones. A cell
# past its outer block's last live block (the first rows of the
# sequence, whose band is cut by its start) is skipped and its index
# clamped to the block before, so it moves nothing. Of a band's blocks
# the one on the diagonal and the one on the band's lower edge pay for a
# mask; the blocks between are wholly visible. Every edge below is
# written from the rows it stands for (a block's first row, its last,
# the window's far end), in blocks of ``blk`` rows each way.

def _keys_of(iq, blk, window, most=jnp.maximum):
    """(first, last) live key block of q-block ``iq``: the blocks of the
    first key its first row sees and of its last row (``most``: the
    built-in ``max`` where ``iq`` is a Python number)."""
    return (most(iq * blk - (window - 1), 0) // blk,
            (iq * blk + blk - 1) // blk)


def _queries_of(ik, blk, window, nb, least=jnp.minimum):
    """(first, last) live q-block of key block ``ik``, of ``nb``: the
    blocks of its first key's own row and of the last row that sees its
    last key."""
    return ((ik * blk) // blk,
            least((ik * blk + blk + window - 2) // blk, nb - 1))


def _wholly_seen(iq, ik, blk, window):
    """No entry of the block pair is masked: it lies under the
    diagonal and above the band's lower edge."""
    return ((ik * blk + blk - 1 <= iq * blk)
            & (ik * blk > iq * blk + blk - 1 - window))


def _band(t, blk, window):
    """(cells of a multi-block grid's innermost dimension, block pairs a
    head walks) under ``window``: the most live key blocks a q-block
    has, which is also the most live q-blocks a key block has (blocks
    are square), and the live pairs. With None the causal grid's: every
    block a cell, the pairs at or under the diagonal walked."""
    nb = t // blk
    if window is None:
        return nb, nb * (nb + 1) // 2
    spans = (_keys_of(i, blk, window, max) for i in range(nb))
    keys = [last - first + 1 for first, last in spans]
    return max(keys), sum(keys)


def _key_cell(iq, cell, *, blk, causal, window):
    """(key block, is it live) of cell ``cell`` of q-block ``iq``'s
    innermost grid dimension: the cell's own number, live at or under
    the diagonal; under a window the band's first block plus the cell,
    live up to the band's last."""
    if window is None:
        return cell, (not causal) or (cell * blk <= iq * blk + blk - 1)
    first, last = _keys_of(iq, blk, window)
    return first + cell, first + cell <= last


def _query_cell(ik, cell, *, blk, causal, window, nb):
    """The same for key block ``ik``'s q-blocks (the backward kernel)."""
    if window is None:
        return cell, (not causal) or (ik * blk <= cell * blk + blk - 1)
    first, last = _queries_of(ik, blk, window, nb)
    return first + cell, first + cell <= last


def _on_live(iq, ik, live, body, *, blk, causal, window):
    """``body(masked)`` for a ``live`` block pair (``iq``, ``ik``).
    Without a window every live pair is masked where the row is causal;
    under one only a pair that straddles the diagonal or the band's
    lower edge is. (A row whose window opens past the band's first
    block meets that block wholly masked: the forward's p are exp(0)
    there, and the first block with a key the row sees wipes them,
    corr = 0.)"""
    if window is None:
        pl.when(live)(functools.partial(body, causal))
        return
    whole = _wholly_seen(iq, ik, blk, window)
    pl.when(live & whole)(functools.partial(body, False))
    pl.when(live & jnp.logical_not(whole))(functools.partial(body, True))


# Rows of a causal slab on the single-block path (``_causal_slabs``).
_SLAB_ROWS = 256


def _causal_slabs(t: int, causal: bool) -> int:
    """Row slabs a single-block body walks its [t, t] block in; 1 where
    it computes the square.

    Under ``causal`` the body computes the triangle, not the square:
    the block's rows are cut into ``n`` static slabs of ``_SLAB_ROWS``
    and slab ``i`` reads only the keys up to its own diagonal,
    ``[0, (i + 1) * _SLAB_ROWS)`` — (n + 1) / 2n of the square's matmul
    passes, exps and selects. Decided from ``causal`` and ``t`` alone:
    where three or more such slabs tile the row, that many; else — not
    causal, or a row too short or too odd for them — one: the square.

    Why 256 rows, and three (one v5e chip, 32 x 12 heads of 64, forward
    + backward of a layer in ms; PERF.md section 6, PR 31). At t=1024:
    the square 1.18 + 2.76, two slabs of 512 0.94 + 2.17, four of 256
    0.93 + 1.86, eight of 128 1.19 + 2.44: a slab of S rows latches k's
    and v's 128 x 128 tiles into the array once more and streams only S
    rows past each, which at 128 costs what the triangle saves. At t=768
    three slabs 0.59 + 1.17 against 0.78 + 1.57; at t=512 two slabs
    0.49 + 0.74 against 0.38 + 0.74: no gain, so the square stays.
    Callers ask for a row under a ``window`` as for one that is not
    causal: it takes the square, with the band masked in it (a test's
    window, a tiny preset's: the models' are longer than a block)."""
    if causal and t % _SLAB_ROWS == 0 and t >= 3 * _SLAB_ROWS:
        return t // _SLAB_ROWS
    return 1


def _slab_scores(q, k, *, scale, causal, window=None):
    """Scaled q·kᵀ of one row slab, [S, c]: its S rows are the last S
    of the c columns it is given, so the causal mask can bite only in
    the last S columns (the slab's diagonal square, the same lower
    triangle for every slab) and is applied nowhere else. A ``window``
    comes with the one slab that is the square (``_causal_slabs``):
    the band's lower edge is cut out of the same triangle."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        rows, cols = s.shape
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
        below = row >= col
        if window is not None:
            assert rows == cols, (rows, cols)
            below &= col > row - window
        diag = jnp.where(below, s[:, cols - rows:], _NEG_INF)
        s = diag if cols == rows else jnp.concatenate(
            [s[:, :cols - rows], diag], axis=1)
    return s


# ---------------------------------------------------------------------------
# the kernels' view of their operands
# ---------------------------------------------------------------------------
#
# Every kernel below takes q, k, v (and do, o) as [N, T, G*L]: N rows
# of the grid's first dimension, G blocks of L lanes along the last.
# A block holds ``hpb`` heads of width ``d`` side by side (L = hpb*d),
# which the body walks as static lane slices. ``lse`` (and ``delta``)
# are [N, G, T/blk, hpb, blk] float32, a q-block's rows along the lanes:
# a [blk, 1] column, the shape the scores broadcast against, fills one
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


def _delta(o_ref, do_ref, sl, rows=slice(None)):
    """The row sums of o * do for the head in lanes ``sl``: [rows, 1],
    made in the kernel from blocks it holds, never an array in HBM."""
    return jnp.sum(o_ref[0, rows, sl].astype(jnp.float32)
                   * do_ref[0, rows, sl].astype(jnp.float32),
                   axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, blk, nk, d, hpb,
                causal, window=None):
    """``nk``: the cells of the innermost grid dimension, every key
    block or, under a ``window``, the band's (``_band``)."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def attend(kb, masked):
        for j, sl in enumerate(_head_slices(d, hpb)):
            q = q_ref[0, :, sl]                # [blk, d]
            k = k_ref[0, :, sl]
            v = v_ref[0, :, sl]
            s = _masked_scores(q, k, iq, kb, scale=scale, blk=blk,
                               causal=masked, window=window)

            m_prev = m_ref[j]                  # [blk, 128] (replicated)
            block_max = jnp.max(s, axis=-1, keepdims=True)  # [blk, 1]
            m_new = jnp.maximum(m_prev, jnp.broadcast_to(
                block_max, m_prev.shape))
            corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])    # [blk, 1]
            p = jnp.exp(s - m_new[:, :1])                   # [blk, blk]
            l_ref[j] = l_ref[j] * corr + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), m_prev.shape)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [blk, d]
            acc_ref[:, sl] = acc_ref[:, sl] * corr + pv
            m_ref[j] = m_new

    # Blocks above the diagonal, and below a window's band, are skipped.
    where = dict(blk=blk, causal=causal, window=window)
    kb, live = _key_cell(iq, ik, **where)
    _on_live(iq, kb, live, functools.partial(attend, kb), **where)

    @pl.when(ik == nk - 1)
    def _finalize():
        for j, sl in enumerate(_head_slices(d, hpb)):
            l = l_ref[j][:, :1]
            o_ref[0, :, sl] = (acc_ref[:, sl] / jnp.maximum(
                l, 1e-30)).astype(o_ref.dtype)
            lse_ref[j:j + 1] = _as_row(m_ref[j][:, :1] + jnp.log(
                jnp.maximum(l, 1e-30))).astype(lse_ref.dtype)


def _slab_rows(t, slabs):
    return [slice(i * (t // slabs), (i + 1) * (t // slabs))
            for i in range(slabs)]


def _fwd_single_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                       *, scale, t, d, hpb, causal, slabs, window=None):
    """Single-block forward: the whole row fits one block, so plain
    (one-pass) softmax replaces the streaming max/sum scratch state —
    fewer VPU ops and no cross-iteration scratch.

    The block is walked as ``slabs`` static row slabs (``_causal_slabs``)
    and slab i meets only the keys and values up to its diagonal: the
    whole of a row's scores is still in hand, so the softmax stays one
    pass. One slab is the square. A head's slabs are written out phase
    by phase, not slab by slab: Mosaic schedules close to the order it
    is given, and a slab's row maximum, a reduction across lanes,
    stands between its two matmuls — slab by slab the array waits for
    it (1.10 ms a GPT-2 layer against 0.93 so)."""
    rows = _slab_rows(t, slabs)
    for h, sl in enumerate(_head_slices(d, hpb)):
        s = [_slab_scores(q_ref[0, r, sl], k_ref[0, :r.stop, sl],
                          scale=scale, causal=causal, window=window)
             for r in rows]
        m = [jnp.max(x, axis=-1, keepdims=True) for x in s]  # [S, 1]
        p = [jnp.exp(x - m_i) for x, m_i in zip(s, m)]
        l = [jnp.maximum(jnp.sum(x, axis=-1, keepdims=True), 1e-30)
             for x in p]
        for r, p_i, l_i in zip(rows, p, l):
            v = v_ref[0, :r.stop, sl]
            o = jax.lax.dot_general(
                p_i.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[0, r, sl] = (o / l_i).astype(o_ref.dtype)
        for r, m_i, l_i in zip(rows, m, l):
            lse_ref[h:h + 1, r] = _as_row(m_i + jnp.log(l_i)).astype(
                lse_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "blk", "d", "hpb", "interpret", "window"))
def _flash_fwd(q, k, v, *, scale, causal, blk, d, hpb, interpret,
               window=None):
    """(out [N, T, G*L], lse [N, G, T/blk, hpb, blk]). Under ``jax.jit``
    so that a model's layers, which call it at one shape, trace and
    lower it once a trace of the step and share one ``func.func``: XLA
    inlines the calls again, each under its caller's scope. Takes
    ``_Static`` whole."""
    n, t, w = q.shape
    lanes = d * hpb
    g = w // lanes
    nb = t // blk
    out_shape = [jax.ShapeDtypeStruct((n, t, w), q.dtype),
                 jax.ShapeDtypeStruct((n, g, nb, hpb, blk), jnp.float32)]
    if nb == 1:
        seq = _seq_spec(t, lanes, lambda b, c: (b, 0, c))
        return pl.pallas_call(
            functools.partial(_fwd_single_kernel, scale=scale, t=t,
                              d=d, hpb=hpb, causal=causal,
                              slabs=_causal_slabs(
                                  t, causal and window is None),
                              window=window),
            grid=(n, g),
            in_specs=[seq, seq, seq],
            out_specs=[seq,
                       _stat_spec(hpb, t, lambda b, c: (b, c, 0, 0, 0))],
            out_shape=out_shape,
            interpret=interpret,
        )(q, k, v)
    # key cells: every key block, or under a window the band's, counted
    # from the q-block's first live one and clamped to its last
    if window is None:
        nk = nb
        kv_spec = _seq_spec(blk, lanes, lambda b, c, i, j: (b, j, c))
    else:
        nk = _band(t, blk, window)[0]

        def block(b, c, i, j):
            first, last = _keys_of(i, blk, window)
            return (b, jnp.minimum(first + j, last), c)
        kv_spec = _seq_spec(blk, lanes, block)
    q_spec = _seq_spec(blk, lanes, lambda b, c, i, j: (b, i, c))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, blk=blk,
                          nk=nk, d=d, hpb=hpb, causal=causal,
                          window=window),
        grid=(n, g, nb, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   _stat_spec(hpb, blk, lambda b, c, i, j: (b, c, i, 0, 0))],
        out_shape=out_shape,
        scratch_shapes=[
            _vmem((blk, lanes)),      # acc
            _vmem((hpb, blk, 128)),   # running max (replicated lanes)
            _vmem((hpb, blk, 128)),   # running sum (replicated lanes)
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v)


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params(sequential: int = 1, vmem: int | None = None):
    """The last ``sequential`` of a grid's four dimensions run in order;
    ``vmem``: what the kernel may hold, where that is more than the
    16 MiB a kernel gets unasked."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (4 - sequential)
        + ("arbitrary",) * sequential,
        vmem_limit_bytes=vmem)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, delta_ref,
                *, scale, blk, nb, cells, d, hpb, causal, window=None):
    """The whole backward pass of one block pair of a row of several
    blocks (``blk`` rows each way, ``nb`` of them a row): ``s``, ``p``,
    ``dov`` and ``ds`` made once and the three products fed from them,
    five MXU passes. Grid (batch, lane block, key block, query cell), the
    last two in order; ``cells``: those of the innermost dimension, every
    q-block or, under a ``window``, those of a key block's band, whose
    live q-blocks start at its diagonal and END where the window has
    passed it. In float32 scratch: ``dk`` / ``dv`` of the key block
    across its cells; ``dq`` of the lane block's WHOLE row across the key
    blocks, a q-block's rows zeroed in its first live key block and cast
    and written in its last; ``delta``, made in that first one from the
    ``o`` fetched there alone and never an array in HBM. Sums run in the
    grid's order (key blocks ascending into ``dq``, q-blocks into ``dk``
    / ``dv``): a run gives the last run's gradients to the bit."""
    ik = pl.program_id(2)
    cell = pl.program_id(3)
    where = dict(blk=blk, causal=causal, window=window)
    qb, live = _query_cell(ik, cell, nb=nb, **where)
    first_key = 0 if window is None else _keys_of(qb, blk, window)[0]
    last_key = qb if causal else nb - 1     # equal blocks: the diagonal
    q_rows = pl.ds(pl.multiple_of(qb * blk, blk), blk)
    heads = _head_slices(d, hpb)

    @pl.when(live & (ik == first_key))
    def _first_key_block():
        dq_acc[q_rows] = jnp.zeros((blk, dq_acc.shape[1]), jnp.float32)
        for j, sl in enumerate(heads):
            delta_ref[qb, j:j + 1] = _as_row(_delta(o_ref, do_ref, sl))

    @pl.when(cell == 0)
    def _first_cell():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked):
        for j, sl in enumerate(heads):
            q = q_ref[0, :, sl]
            k = k_ref[0, :, sl]
            v = v_ref[0, :, sl]
            do = do_ref[0, :, sl]              # bf16 operand for the MXU
            lse = _as_col(lse_ref[j:j + 1])                 # [blk, 1]
            delta = _as_col(delta_ref.at[qb][j:j + 1])      # [blk, 1]
            s = _masked_scores(q, k, qb, ik, scale=scale, blk=blk,
                               causal=masked, window=window)
            p = jnp.exp(s - lse)                            # [blk, blk]
            dv_acc[:, sl] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [blk, d]
            dov = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (p * (dov - delta) * scale).astype(q.dtype)
            dk_acc[:, sl] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_acc[q_rows, sl] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _on_live(qb, ik, live, step, **where)

    @pl.when(live & (ik == last_key))   # no later key block reaches them
    def _dq_whole():
        dq_ref[0] = dq_acc[q_rows].astype(dq_ref.dtype)

    @pl.when(cell == cells - 1)
    def _dkv_whole():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_blocks(nb, blk, causal, window):
    """``_bwd_kernel``'s grid over ``nb`` blocks each way: (cells of the
    innermost dimension, then three maps from (key block, cell) to the
    q-block whose q, dO and statistics the cell reads, to the one whose
    ``o`` it reads, and to the one whose ``dq`` it may fill). A dead
    cell's q-block is clamped to a live one, so it moves nothing; ``o``
    is fetched where a q-block meets its first live key block (delta)
    and stays on the last one fetched elsewhere; ``dq``'s block is that
    of the q-block whose last live key block this is (the diagonal's),
    so each is written to HBM once, when the index moves on."""
    cells = _band(nb * blk, blk, window)[0]

    def last_q(j):      # the last live q-block of key block j
        if window is None:
            return nb - 1
        return _queries_of(j, blk, window, nb)[1]

    def q_block(j, i):
        if window is None:
            return jnp.maximum(i, j) if causal else i
        return jnp.minimum(j + i, last_q(j))

    def o_block(j, i):
        qb = q_block(j, i)
        return jnp.where((j == 0) | (qb == last_q(j)), qb, last_q(j - 1))

    def dq_block(j, i):
        return j if causal else jnp.where(j == nb - 1, i, 0)
    return cells, q_block, o_block, dq_block


# What ``_bwd_kernel`` may hold in VMEM (a v5e has 128 MiB).
_BWD_VMEM = 64 * 1024 * 1024


def _bwd_bytes(t: int, blk: int, lanes: int) -> int:
    """What ``_bwd_kernel`` holds in VMEM at ``t`` rows in blocks of
    ``blk``, counted from the shapes. What grows with ``t``: the float32
    ``dq`` of a lane block's whole row and delta's rows (a block's heads
    padded to 8 sublanes), 544 bytes a row at 128 lanes. What a cell
    holds whatever ``t`` is: the five 2-byte operand blocks and the
    three output blocks twice over, the statistics' block twice,
    ``dk``'s and ``dv``'s accumulators, and four [blk, blk] float32
    squares for the body's scores, probabilities and cotangents."""
    lanes = -(-lanes // 128) * 128
    resident = t * 4 * (lanes + 8)
    cell = (4 * blk * blk * 4
            + 2 * 2 * blk * 8 * lanes
            + 2 * 4 * 8 * blk
            + 2 * blk * lanes * 4)
    return resident + cell


def _bwd_fits(t: int, blk: int, lanes: int) -> bool:
    return _bwd_bytes(t, blk, lanes) <= _BWD_VMEM


def _row_past_the_budget(kernel: str, t: int, lanes: int, asked: int,
                         budget: int) -> NotImplementedError:
    """What ``flash_attention`` and ``mla_flash_static`` raise (module
    docstring: why no ``ValueError``)."""
    return NotImplementedError(
        f"a row of {t} rows at {lanes} lanes: {kernel} keeps dq of the "
        f"whole row in VMEM and would hold {asked} bytes of a budget of "
        f"{budget}. Split the sequence over an `sp` mesh axis, or keep "
        "dq resident over the band's q-rows alone (ROADMAP C4)")


def _bwd_fused_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, *acc, scale, t, d, hpb,
                      causal, slabs, window=None):
    """Single-block backward (t fits one block): the score matrix made
    ONCE for dq, dk and dv. Walked as the forward is: ``slabs`` static
    row slabs that stop at the diagonal, a head's slabs phase by phase.
    A slab's dq is whole; its shares of dk and dv (the keys it read) are
    summed over the slabs in float32 VMEM scratch ``acc`` = (dk_acc,
    dv_acc) and cast once — the last slab, which reads every key, goes
    first and initialises them. One slab (the square) has nothing to sum
    and takes no scratch."""
    rows = _slab_rows(t, slabs)[::-1]
    for h, sl in enumerate(_head_slices(d, hpb)):
        lse = [_as_col(lse_ref[h:h + 1, r]) for r in rows]  # [S, 1]
        delta = [_delta(o_ref, do_ref, sl, r) for r in rows]
        s = [_slab_scores(q_ref[0, r, sl], k_ref[0, :r.stop, sl],
                          scale=scale, causal=causal, window=window)
             for r in rows]
        dov = [jax.lax.dot_general(
                   do_ref[0, r, sl], v_ref[0, :r.stop, sl],
                   (((1,), (1,)), ((), ())),
                   preferred_element_type=jnp.float32)     # [S, c]
               for r in rows]
        p = [jnp.exp(x - lse_i) for x, lse_i in zip(s, lse)]
        # bf16 operands into the MXU (f32 operands run it at a
        # fraction of peak); accumulation stays f32.
        ds = [(p_i * (dov_i - delta_i) * scale).astype(q_ref.dtype)
              for p_i, dov_i, delta_i in zip(p, dov, delta)]
        for r, p_i, ds_i in zip(rows, p, ds):
            keys = slice(0, r.stop)
            do = do_ref[0, r, sl]
            dv = jax.lax.dot_general(
                p_i.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [c, d]
            dq_ref[0, r, sl] = jax.lax.dot_general(
                ds_i, k_ref[0, keys, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dq_ref.dtype)
            dk = jax.lax.dot_general(
                ds_i, q_ref[0, r, sl], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [c, d]
            if slabs == 1:
                dk_ref[0, :, sl] = dk.astype(dk_ref.dtype)
                dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)
            elif r.stop == t:
                acc[0][:, sl] = dk
                acc[1][:, sl] = dv
            else:
                acc[0][keys, sl] += dk
                acc[1][keys, sl] += dv
    if slabs > 1:
        dk_ref[0] = acc[0][...].astype(dk_ref.dtype)
        dv_ref[0] = acc[1][...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "blk", "d", "hpb", "interpret", "window"))
def _flash_bwd(q, k, v, out, lse, g, *, scale, causal, blk, d, hpb,
               interpret, window=None):
    """(dq, dk, dv), each [N, T, G*L]; jitted for the reason
    ``_flash_fwd`` is. One kernel: the fused body for a row of one
    block, ``_bwd_kernel`` for a row of several (whose resident rows
    ``flash_attention`` has held to ``_bwd_fits``)."""
    n, t, w = q.shape
    lanes = d * hpb
    ng = w // lanes
    nb = t // blk
    do = g.astype(q.dtype)
    grads = [jax.ShapeDtypeStruct((n, t, w), x.dtype) for x in (q, k, v)]
    if nb == 1:
        seq = _seq_spec(t, lanes, lambda b, c: (b, 0, c))
        slabs = _causal_slabs(t, causal and window is None)
        return pl.pallas_call(
            functools.partial(_bwd_fused_kernel, scale=scale, t=t,
                              d=d, hpb=hpb, causal=causal, slabs=slabs,
                              window=window),
            grid=(n, ng),
            in_specs=[seq, seq, seq, seq, seq,
                      _stat_spec(hpb, t, lambda b, c: (b, c, 0, 0, 0))],
            out_specs=[seq, seq, seq],
            out_shape=grads,
            scratch_shapes=[_vmem((t, lanes))] * 2 if slabs > 1 else [],
            interpret=interpret,
        )(q, k, v, out, do, lse)

    cells, q_block, o_block, dq_block = _bwd_blocks(nb, blk, causal, window)
    q_spec = _seq_spec(
        blk, lanes, lambda b, c, j, i: (b, q_block(j, i), c))
    kv_spec = _seq_spec(blk, lanes, lambda b, c, j, i: (b, j, c))
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, blk=blk, nb=nb,
                          cells=cells, d=d, hpb=hpb, causal=causal,
                          window=window),
        grid=(n, ng, nb, cells),
        in_specs=[q_spec, kv_spec, kv_spec,
                  _seq_spec(blk, lanes,
                            lambda b, c, j, i: (b, o_block(j, i), c)),
                  q_spec,
                  _stat_spec(hpb, blk, lambda b, c, j, i: (
                      b, c, q_block(j, i), 0, 0))],
        out_specs=[_seq_spec(blk, lanes,
                             lambda b, c, j, i: (b, dq_block(j, i), c)),
                   kv_spec, kv_spec],
        out_shape=grads,
        scratch_shapes=[_vmem((t, lanes)), _vmem((blk, lanes)),
                        _vmem((blk, lanes)), _vmem((nb, hpb, blk))],
        compiler_params=_compiler_params(2, _BWD_VMEM),
        interpret=interpret,
    )(q, k, v, out, do, lse))


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

class _Static(NamedTuple):
    """What the kernels are specialised on, besides their shapes."""
    scale: float
    causal: bool
    blk: int        # rows of a block, queries and keys alike
    d: int          # head width
    hpb: int        # heads in a lane block
    interpret: bool
    window: int | None = None   # keys a row sees, itself among them

    @property   # the name benchmark/tools/smallthinker_limit.py reads
    def bk(self) -> int:
        return self.blk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_core(q, k, v, static: _Static):
    return _flash_fwd(q, k, v, **static._asdict())[0]


def _flash_core_fwd(q, k, v, static):
    out, lse = name_core_results(
        *_flash_fwd(q, k, v, **static._asdict()))
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
                    block: int | None = None,
                    interpret: bool = False,
                    window: int | None = None) -> jax.Array:
    """Flash attention on [B, T, H, D]; differentiable (custom VJP).

    ``window``: a row sees its last ``window`` keys, itself among them
    (``t - window < j <= t``: the sliding-window layers of a
    window/global stack); None, or a window no shorter than the row, is
    plain causal attention. The multi-block grids run over the band
    alone, in both passes (``_band``'s comment), in blocks of
    ``_window_block``'s choice (the window's own 512 rows under 512
    keys); a call given a window says so in the notes (``_band_notes``):
    ``flash_window`` (``"none"`` where the row is no longer than it),
    ``flash_block_rows``, ``flash_band_blocks``, ``flash_band_area``. In
    a stack of both kinds the notes are the last windowed call's.

    Falls back to the caller's dense path when shapes don't block
    cleanly — check with ``flash_attention_shapes_ok`` or catch
    ValueError. A row of several blocks too long for the backward
    kernel's VMEM (``_bwd_fits``) is no such shape: it raises
    ``NotImplementedError``, before anything is traced or noted.
    ``block``: the rows of a block in place of ``_window_block``'s, the
    tests' way to the multi-block grids at sizes the CPU interprets.

    The kernels read q, k, v where the projections wrote them, 128
    lanes of [B, T, H*D] a block (``_heads_per_block``); shapes that
    cannot be blocked so are folded to [B*H, T, D], at a transpose each
    way. Which of the two ran is in the notes (``flash_layout``).
    """
    b, t, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    asked = window is not None
    if asked:
        if not causal or window < 1:
            raise ValueError(f"window {window} (causal={causal}): a "
                             "window is the last `window` keys of a "
                             "causal row")
        window = None if window >= t else int(window)
    blk = block or _window_block(t, window)
    if blk == 0 or t % blk:
        raise ValueError(f"seq len {t} not divisible into flash blocks")
    direct = _heads_per_block(h, d)
    hpb = direct or 1           # folded: one head a block
    single = blk == t
    if not single and not _bwd_fits(t, blk, d * hpb):
        raise _row_past_the_budget(
            "the backward kernel", t, d * hpb,
            _bwd_bytes(t, blk, d * hpb), _BWD_VMEM)
    # Made here and not in the jitted functions: jax caches their
    # traces, so the step's second trace would find no note.
    tracing.note_trace(
        flash_layout="bthd" if direct else "folded",
        flash_lanes_per_block=d * hpb,
        flash_path="single_block" if single else "multi_block",
        flash_causal_slabs=_causal_slabs(t, causal and window is None)
        if single else 1)
    if asked:   # a call without a window leaves the notes it left
        tracing.note_trace(flash_window=window or "none",
                           **_band_notes(t, blk, window))
    if not single:  # dq's accumulator holds the whole row in VMEM
        tracing.note_trace(flash_bwd_resident_rows=t)
    static = _Static(float(scale), causal, blk, d, hpb, interpret, window)
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


# ---------------------------------------------------------------------------
# latent attention: keys of two parts, one of them shared by every head
# ---------------------------------------------------------------------------
#
# Multi-head latent attention (DeepSeek-V2/V3's MLA, JoyAI-LLM-Flash's)
# as the training pass sees it: head i's query is [q_nope_i | q_rope_i]
# (dn + dr wide: 128 + 64), its key [k_nope_i | k_r] where the rotary
# part ``k_r`` is ONE head that all the heads share, its value dn wide.
# The kernels take the five operands as the projections wrote them:
#
#   qn, kn, v   [B, T, H*dn]   one head a 128-lane block, as D=128 above
#   qr          [B, T, H*dr]   the heads' rotary queries side by side
#   kr          [B, T, dr]     the shared rotary key, never repeated
#
# and add the two score matmuls in the body, before the softmax:
# ``s = (qn kn^T + qr kr^T) * scale``. No operand is padded to 256 and
# no [B, T, H*(dn+dr)] key exists anywhere. A grid cell holds ``hpb =
# 128 // dr`` heads (two), so that its block of ``qr`` is whole 128-lane
# tiles; ``kr``'s block is the array's full 64 lanes. The grids are the
# multi-block ones above, with two differences: the block that
# straddles the diagonal is the only one that pays for a mask, and the
# index maps stop at the diagonal, so a skipped cell moves nothing.
# Statistics and outputs are laid out as above.
#
# The backward pass is one kernel, grid (batch, head pair, key block,
# query block), the last three in order. A cell makes ``s``, ``p``,
# ``dP`` and ``ds`` of its block pair once and feeds all five products
# from them. What stays in VMEM scratch across cells, in float32, and is
# cast and written once (and bounds the row: ``_mla_bwd_fits``):
#
#   dk_n, dv   [block, 256]     of the key block, across its query blocks
#   dq_n, dq_r [T, 256 + 128]   of the head pair's WHOLE sequence, across
#                               the key blocks; a query block's rows are
#                               final at its diagonal and leave there
#   dk_r       [T, 64]          the sum over every head, across the head
#                               pairs; leaves in the last pair's cells
#   delta      [T/block, 2, block]  rowsum(o * dO), made in the first key
#                               block's cells, which every query block
#                               passes
#
# An output block is indexed by the key block of the cell that fills it
# (``dq``: the diagonal cell; ``dk_r``: block 0 until the last head
# pair), so each is written to HBM once, when its index moves on.

def _mla_scores(qn, qr, kn, kr, scale, on_diagonal: bool):
    """[blk, blk] scaled scores of one head: the two parts' products
    summed; on the diagonal block the causal mask."""
    dims = (((1,), (1,)), ((), ()))
    s = (jax.lax.dot_general(qn, kn, dims,
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qr, kr, dims,
                               preferred_element_type=jnp.float32)) * scale
    if on_diagonal:
        below = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                 >= jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        s = jnp.where(below, s, _NEG_INF)
    return s


def _mla_parts(dn, dr, hpb):
    """(lanes of the nope / value block, lanes of the rope block) for
    each head of a grid cell."""
    return [(slice(j * dn, (j + 1) * dn), slice(j * dr, (j + 1) * dr))
            for j in range(hpb)]


def _on_live_blocks(iq, ik, body):
    """``body(on_diagonal)`` for the blocks at or below the diagonal."""
    pl.when(ik < iq)(functools.partial(body, False))
    pl.when(ik == iq)(functools.partial(body, True))


def _mla_fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                    acc_ref, m_ref, l_ref, *, scale, nk, dn, dr, hpb):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def attend(on_diagonal):
        kr = kr_ref[0]
        for j, (n, r) in enumerate(_mla_parts(dn, dr, hpb)):
            v = v_ref[0, :, n]
            s = _mla_scores(qn_ref[0, :, n], qr_ref[0, :, r],
                            kn_ref[0, :, n], kr, scale, on_diagonal)
            m_prev = m_ref[j]                  # [blk, 128] (replicated)
            m_new = jnp.maximum(m_prev, jnp.broadcast_to(
                jnp.max(s, axis=-1, keepdims=True), m_prev.shape))
            corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])    # [blk, 1]
            p = jnp.exp(s - m_new[:, :1])                   # [blk, blk]
            l_ref[j] = l_ref[j] * corr + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), m_prev.shape)
            acc_ref[:, n] = acc_ref[:, n] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[j] = m_new

    _on_live_blocks(iq, ik, attend)

    @pl.when(ik == nk - 1)
    def _finalize():
        for j, (n, _) in enumerate(_mla_parts(dn, dr, hpb)):
            l = jnp.maximum(l_ref[j][:, :1], 1e-30)
            o_ref[0, :, n] = (acc_ref[:, n] / l).astype(o_ref.dtype)
            lse_ref[j:j + 1] = _as_row(
                m_ref[j][:, :1] + jnp.log(l)).astype(lse_ref.dtype)


def _mla_bwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, do_ref,
                    lse_ref, dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                    dqn_acc, dqr_acc, dkn_acc, dkr_acc, dv_acc, delta_ref,
                    *, scale, ng, nb, blk, dn, dr, hpb):
    """The whole backward pass of one (head pair, key block, query
    block): a head's probabilities (from the saved log-sum-exp) and its
    scores' cotangent ``ds`` made once, five products from them."""
    c = pl.program_id(1)
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    q_rows = pl.ds(pl.multiple_of(iq * blk, blk), blk)
    k_rows = pl.ds(pl.multiple_of(ik * blk, blk), blk)
    parts = _mla_parts(dn, dr, hpb)

    @pl.when(ik == 0)
    def _first_key_block():         # every query block passes here first
        dqn_acc[q_rows] = jnp.zeros((blk, hpb * dn), jnp.float32)
        dqr_acc[q_rows] = jnp.zeros((blk, hpb * dr), jnp.float32)
        for j, (n, _) in enumerate(parts):
            delta_ref[iq, j:j + 1] = _as_row(_delta(o_ref, do_ref, n))

    @pl.when(iq == ik)
    def _first_query_block():       # the key block's first live cell
        dkn_acc[...] = jnp.zeros_like(dkn_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when((iq == ik) & (c == 0))
    def _first_head_pair():
        dkr_acc[k_rows] = jnp.zeros((blk, dr), jnp.float32)

    def step(on_diagonal):
        kr = kr_ref[0]
        for j, (n, r) in enumerate(parts):
            s = _mla_scores(qn_ref[0, :, n], qr_ref[0, :, r],
                            kn_ref[0, :, n], kr, scale, on_diagonal)
            p = jnp.exp(s - _as_col(lse_ref[j:j + 1]))
            dov = jax.lax.dot_general(
                do_ref[0, :, n], v_ref[0, :, n], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dov - _as_col(delta_ref.at[iq][j:j + 1])) * scale
            ds = ds.astype(qn_ref.dtype)    # the matmuls' operand type
            do = do_ref[0, :, n]
            over_q = (((0,), (0,)), ((), ()))      # [blk, blk]^T [blk, d]
            over_k = (((1,), (0,)), ((), ()))      # [blk, blk] [blk, d]
            dv_acc[:, n] += jax.lax.dot_general(
                p.astype(do.dtype), do, over_q,
                preferred_element_type=jnp.float32)
            dkn_acc[:, n] += jax.lax.dot_general(
                ds, qn_ref[0, :, n], over_q,
                preferred_element_type=jnp.float32)
            dkr_acc[k_rows] += jax.lax.dot_general(
                ds, qr_ref[0, :, r], over_q,
                preferred_element_type=jnp.float32)
            dqn_acc[q_rows, n] += jax.lax.dot_general(
                ds, kn_ref[0, :, n], over_k,
                preferred_element_type=jnp.float32)
            dqr_acc[q_rows, r] += jax.lax.dot_general(
                ds, kr, over_k, preferred_element_type=jnp.float32)

    _on_live_blocks(iq, ik, step)

    @pl.when(iq == ik)              # no later key block reaches these rows
    def _dq_whole():
        dqn_ref[0] = dqn_acc[q_rows].astype(dqn_ref.dtype)
        dqr_ref[0] = dqr_acc[q_rows].astype(dqr_ref.dtype)

    @pl.when(iq == nb - 1)
    def _dkv_whole():
        dkn_ref[0] = dkn_acc[...].astype(dkn_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((iq == nb - 1) & (c == ng - 1))
    def _dkr_whole():
        dkr_ref[0] = dkr_acc[k_rows].astype(dkr_ref.dtype)


class _MlaStatic(NamedTuple):
    """What the latent-attention kernels are specialised on."""
    scale: float
    block: int      # rows of a block, queries and keys alike
    dn: int         # width of q_nope, k_nope and v of a head
    dr: int         # width of the rotary parts
    interpret: bool

    @property
    def hpb(self) -> int:
        return 128 // self.dr


# What a latent-attention kernel may hold in VMEM: a block of 1,024 rows
# of two heads needs more than the 16 MiB that a kernel gets unasked
# (two [1024, 1024] float32 score squares and their casts beside the
# double-buffered operands). A v5e has 128 MiB.
_MLA_VMEM = 64 * 1024 * 1024
_MLA_BWD_VMEM = 100 * 1024 * 1024       # the backward kernel's


def _mla_bwd_bytes(t: int, blk: int, dn: int, dr: int) -> int:
    """What ``_mla_bwd_kernel`` holds in VMEM at ``t`` rows, counted
    from the shapes. What grows with ``t``: the float32 accumulators of
    a head pair's whole sequence (dq_n, dq_r, and dk_r padded to 128
    lanes) and delta's rows (a pair's 2 padded to 8 sublanes): 2,080
    bytes a row at the published widths. What a cell holds whatever
    ``t`` is: as ``_bwd_bytes`` counts it, the squares twice what the
    compiler was seen to take (at 8,192 rows in blocks of 1,024 it
    allocates 35.3 MiB where this counts 43.3; PERF.md 6, PR 35)."""
    hpb = 128 // dr
    wide, rope = hpb * dn, hpb * dr
    resident = t * 4 * (wide + rope + 128 + 8)
    cell = (4 * blk * blk * 4
            + 2 * 2 * blk * (7 * wide + 2 * rope + 2 * 128)
            + 2 * blk * wide * 4)
    return resident + cell


def _mla_bwd_fits(t: int, blk: int, dn: int, dr: int) -> bool:
    # 32,768 rows fit at the published widths, 65,536 do not
    return _mla_bwd_bytes(t, blk, dn, dr) <= _MLA_BWD_VMEM


@functools.partial(jax.jit, static_argnames=("static",))
def mla_flash_fwd(qn, qr, kn, kr, v, *, static: _MlaStatic):
    """(out [B, T, H*dn], lse [B, G, T/b, hpb, b]); jitted for the
    reason ``_flash_fwd`` is."""
    scale, blk, dn, dr, interpret = static
    hpb = static.hpb
    b, t, w = qn.shape
    g, nb = w // (hpb * dn), t // blk
    # index maps stop at the diagonal: a block above it is not fetched
    q_map = lambda b, c, i, j: (b, i, c)                      # noqa: E731
    kv_map = lambda b, c, i, j: (b, jnp.minimum(j, i), c)     # noqa: E731
    kr_map = lambda b, c, i, j: (b, jnp.minimum(j, i), 0)     # noqa: E731
    return pl.pallas_call(
        functools.partial(_mla_fwd_kernel, scale=scale, nk=nb, dn=dn,
                          dr=dr, hpb=hpb),
        grid=(b, g, nb, nb),
        in_specs=[_seq_spec(blk, hpb * dn, q_map),
                  _seq_spec(blk, hpb * dr, q_map),
                  _seq_spec(blk, hpb * dn, kv_map),
                  _seq_spec(blk, dr, kr_map),
                  _seq_spec(blk, hpb * dn, kv_map)],
        out_specs=[_seq_spec(blk, hpb * dn, q_map),
                   _stat_spec(hpb, blk, lambda b, c, i, j: (b, c, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, t, w), qn.dtype),
                   jax.ShapeDtypeStruct((b, g, nb, hpb, blk), jnp.float32)],
        scratch_shapes=[_vmem((blk, hpb * dn)), _vmem((hpb, blk, 128)),
                        _vmem((hpb, blk, 128))],
        compiler_params=_compiler_params(1, _MLA_VMEM),
        interpret=interpret,
    )(qn, qr, kn, kr, v)


@functools.partial(jax.jit, static_argnames=("static",))
def mla_flash_bwd(qn, qr, kn, kr, v, out, lse, g, *, static: _MlaStatic):
    """(dqn, dqr, dkn, dkr, dv), shaped as the operands."""
    scale, blk, dn, dr, interpret = static
    hpb = static.hpb
    b, t, w = qn.shape
    ng, nb = w // (hpb * dn), t // blk
    do = g.astype(qn.dtype)
    wide, rope = hpb * dn, hpb * dr
    # the index maps stop at the diagonal; ``o`` is read in the first
    # key block's cells alone (delta)
    q_map = lambda b, c, j, i: (b, jnp.maximum(i, j), c)      # noqa: E731
    kv_map = lambda b, c, j, i: (b, j, c)                     # noqa: E731
    kr_map = lambda b, c, j, i: (b, j, 0)                     # noqa: E731
    o_map = lambda b, c, j, i: (                              # noqa: E731
        b, jnp.where(j == 0, i, nb - 1), c)
    dkr_map = lambda b, c, j, i: (                            # noqa: E731
        b, jnp.where(c == ng - 1, j, 0), 0)
    stat = _stat_spec(hpb, blk, lambda b, c, j, i: (
        b, c, jnp.maximum(i, j), 0, 0))
    return tuple(pl.pallas_call(
        functools.partial(_mla_bwd_kernel, scale=scale, ng=ng, nb=nb,
                          blk=blk, dn=dn, dr=dr, hpb=hpb),
        grid=(b, ng, nb, nb),
        in_specs=[_seq_spec(blk, wide, q_map),
                  _seq_spec(blk, rope, q_map),
                  _seq_spec(blk, wide, kv_map),
                  _seq_spec(blk, dr, kr_map),
                  _seq_spec(blk, wide, kv_map),
                  _seq_spec(blk, wide, o_map),
                  _seq_spec(blk, wide, q_map), stat],
        out_specs=[_seq_spec(blk, wide, kv_map),
                   _seq_spec(blk, rope, kv_map),
                   _seq_spec(blk, wide, kv_map),
                   _seq_spec(blk, dr, dkr_map),
                   _seq_spec(blk, wide, kv_map)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (qn, qr, kn, kr, v)],
        scratch_shapes=[_vmem((t, wide)), _vmem((t, rope)),
                        _vmem((blk, wide)), _vmem((t, dr)),
                        _vmem((blk, wide)), _vmem((nb, hpb, blk))],
        compiler_params=_compiler_params(3, _MLA_BWD_VMEM),
        interpret=interpret,
    )(qn, qr, kn, kr, v, out, do, lse))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def mla_flash_core(qn, qr, kn, kr, v, static: _MlaStatic):
    """The attention's output [B, T, H*dn]; its backward pass keeps the
    five operands, the output and the log-sum-exp."""
    return mla_flash_fwd(qn, qr, kn, kr, v, static=static)[0]


def _mla_core_fwd(qn, qr, kn, kr, v, static):
    out, lse = name_core_results(
        *mla_flash_fwd(qn, qr, kn, kr, v, static=static))
    return out, (qn, qr, kn, kr, v, out, lse)


def _mla_core_bwd(static, res, g):
    return mla_flash_bwd(*res, g, static=static)


mla_flash_core.defvjp(_mla_core_fwd, _mla_core_bwd)


def mla_flash_shapes_ok(t: int, dn: int, dr: int, dv: int,
                        heads: int) -> bool:
    """Do the latent-attention kernels tile [*, t, heads, dn + dr]
    queries against dv-wide values? One head of dn = dv lanes a 128-lane
    block (or several), the rotary parts of ``128 // dr`` heads another,
    rows in blocks of 128 or more."""
    return (dn == dv and dn % 128 == 0 and dr in (64, 128)
            and heads % (128 // dr) == 0 and _pick_block(t) >= 128)


def mla_flash_static(t: int, dn: int, dr: int, scale: float | None = None,
                     block: int | None = None,
                     interpret: bool = False) -> _MlaStatic:
    """What ``mla_flash_fwd`` / ``mla_flash_bwd`` are specialised on at
    these shapes, with the notes of the path (made here and not in the
    jitted functions, whose traces jax caches). A row whose backward
    accumulators do not fit (``_mla_bwd_fits``) is refused here, before
    a kernel is traced."""
    blk = block or _pick_block(t)
    if blk == 0 or t % blk:
        raise ValueError(f"seq len {t} not divisible into flash blocks")
    static = _MlaStatic(float((dn + dr) ** -0.5 if scale is None else scale),
                        blk, dn, dr, interpret)
    if not _mla_bwd_fits(t, blk, dn, dr):
        raise _row_past_the_budget(
            "latent attention's backward kernel", t,
            static.hpb * (dn + dr), _mla_bwd_bytes(t, blk, dn, dr),
            _MLA_BWD_VMEM)
    tracing.note_trace(
        flash_layout="bthd", flash_lanes_per_block=static.hpb * dn,
        flash_path="mla_multi_block", flash_causal_slabs=1,
        flash_bwd_resident_rows=t)      # dq's rows held in VMEM
    return static


# ---------------------------------------------------------------------------
# the block of a windowed call
# ---------------------------------------------------------------------------
#
# Below every kernel of the file, as the latent kernels are below the
# equal-width ones: a kernel's body is serialized with the lines of the
# frames above it (``flash_attention``'s call of ``_flash_core`` among
# them), so what sits here moves no cell's compile-cache key but those
# of the calls it gives another block.

def _window_block(t: int, window: int | None) -> int:
    """Rows of a block for a row of ``t`` under ``window`` (None: plain
    causal): ``_pick_block(t)``, but the window's own size where a row
    of several blocks has a window shorter than that block which is
    whole 128-row tiles and divides the row. A q-block then meets two
    key blocks of ``window`` rows, not two of ``_pick_block``'s: under
    512 keys at 16,384 rows 63 pairs of 512 x 512 a head where blocks of
    1,024 walk 31 pairs of four times the area for the same band (a
    sliding layer of 64 heads, forward + backward on one v5e chip, 18.7
    ms so against 25.3: PERF.md section 6, PR 57). A window of a block
    or more keeps ``_pick_block``'s: under 4,096 keys blocks of 512
    walk 63 pairs' worth for 70 and lose more than that to the cells'
    own price (30.4 ms a layer of 28 heads against 23.7, same place).
    Decided from the call's shapes alone, as ``_causal_slabs`` is."""
    blk = _pick_block(t)
    if (window is not None and window < blk < t and window % 128 == 0
            and t % window == 0):
        return window
    return blk


def _band_notes(t: int, blk: int, window: int | None) -> dict:
    """What a call given a window says of its geometry: the block pairs
    a head walks (70 at 16,384 rows in blocks of 1,024 under a window of
    4,096, against the causal grid's 136; 63 of 512 under 512), the rows
    of the block it ran in, and the score entries a head computes over
    the entries its mask lets through (a row sees ``min(row + 1,
    window)`` keys): how much dead area is left, 1.25 and 2.0 there. A
    window no shorter than the row is the causal call: the grid's pairs
    at or under the diagonal, or a single block's causal slabs."""
    pairs = _band(t, blk, window)[1]
    slabs = _causal_slabs(t, window is None) if blk == t else 1
    walked = pairs * blk * blk * (slabs + 1) / (2 * slabs)
    w = window or t
    seen = w * (w + 1) // 2 + (t - w) * w
    return dict(flash_band_blocks=pairs, flash_block_rows=blk,
                flash_band_area=round(walked / seen, 3))

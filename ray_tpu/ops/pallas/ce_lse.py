"""The LM head's forward product with the softmax's reduction as its
epilogue: one Pallas TPU kernel that gives each row its log-sum-exp
over the vocabulary and its target's logit, and writes no logit to HBM.

``models/gpt2.py::_chunked_ce_fwd_scan`` makes a chunk's ``[2048, V]``
float32 logits with one matmul, writes them to HBM (412 MB a chunk at
GPT-2's 50,304 rows of the table) and reads them back for
``logsumexp``: 8.75 ms of a 207 ms step for an array that exists to
become one number a row (PERF.md section 6, PR 51). Here a grid cell
makes a ``[strip, tile]`` block of the same logits in VMEM (the same
bfloat16 operands, the same float32 accumulation, float32 ``exp``),
folds it into the rows' running maximum and sum, and drops it:

    m_new = max(m, max(s))
    l     = l * exp(m - m_new) + sum(exp(s - m_new))
    lse   = m + log(l)                      (at the last tile)

Grid: (row block, vocabulary tile), the tiles innermost and in order
("arbitrary"), the row blocks "parallel". A row block ``[rows, E]`` of
hidden states stays in VMEM across the tiles; the table streams past it
once a row block, ``[tile, E]`` at a time, whole ``E`` (768-2,688 in
the cells: no contraction loop). The body walks the row block ``_STRIP``
rows at a time in a ``fori_loop``, so a cell's code is one strip's
matmul and fold whatever the block, and its float32 temporaries are a
strip's.

``m``, ``l`` and the picked logit are kept a lane apart: ``[rows,
128]`` float32 scratch whose lane ``c`` covers the columns ``c`` mod 128
of the table. A tile's eight 128-column parts then fold with whole-vreg
maxima, ``exp``, selects and sums, nothing crosses lanes inside the
loop, and one rescale ``exp(m - m_new)`` serves eight parts; the 128
lanes of a row are joined (the same formula once more) at the last
tile. The target's logit: a lane iota against ``target - tile_start``,
one select a part. Targets come in and ``lse`` / ``picked`` go out as
rows ``[1, rows]``, lanes full in HBM (a ``[N, 1]`` array is 128 times
its size there), turned to and from the loop's columns by a transpose
at a row block's first and last tile.

A table that is no whole number of tiles (32,896 = 257 x 128, a prime;
50,304 = 393 x 128 = 3 x 131 x 128) has a ragged last tile: its block
reads past the table, and the columns past it are masked to ``-inf``
before the maximum. The mask is one select a vreg against a count of
live columns that is the tile's width in every cell but the last: a
second copy of the body for the last tile alone read the same on the
chip and doubled the code. ``blocks`` takes a divisor of ``V`` where a
good one exists, and then there is no mask.

What one v5e chip showed (PERF.md section 6, PR 51; ``scripts/
ce_lse_timing.py``): GPT-2's head, 32,768 rows of 768 against 50,304,
15.1 ms where the scan takes 23.2 and the matmul alone 12.85 at the
MXU's peak; the other seven head shapes of the benchmark likewise under
the scan. Tiles of 1,024 and 2,048 columns read alike at ``E`` 768 and
the narrower better from 2,048 up; strips of 256-1,024 rows within 0.3
ms; the lane-apart ``m`` / ``l`` 0.3-1.0 ms under a ``[rows, 1]`` pair,
the lane-apart ``picked`` and the ``[1, rows]`` ends 0.3 more at GPT-2's
head; picking the target outside the kernel, the table transposed
outside or in the cell, and a strip's fold overlapped by hand with the
next strip's matmul: nothing or worse.

Which programs get the kernel is ``models/gpt2.py::ce_path``'s decision;
the backward pass (a chunk's logits recomputed with this ``lse`` as the
matmul's epilogue) is XLA's and unchanged. The ``pallas_call`` sits
under ``jax.jit`` so that layers of tracing round it (``shard_map``,
``custom_vjp``, ``value_and_grad``) see one equation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_F32 = jnp.float32
_NEG_INF = float("-inf")
# What the kernel may hold: a row block and a table tile, each
# double-buffered, and a strip's float32 logits.
_VMEM_LIMIT = 64 << 20
# The operands' share of it (the two blocks, twice each).
_OPERAND_BYTES = 48 << 20
_STRIP = 512            # rows a matmul of the body makes logits for
_TILE = 1024            # columns of the table a grid cell meets
_ROWS = 4096            # rows a block holds at most


def shapes_ok(n: int, e: int, v: int) -> bool:
    """Whether the kernel tiles ``n`` rows of ``e`` against a table of
    ``v``: whole 128-lane tiles of both widths, whole bfloat16 sublane
    tiles of rows."""
    return e % 128 == 0 and v % 128 == 0 and n % 16 == 0 and n > 0


def blocks(n: int, e: int, v: int) -> tuple[int, int]:
    """(rows of a row block, columns of a vocabulary tile) for ``n``
    rows of ``e`` against ``v``: the shapes decide, nothing else does.

    The tile: ``_TILE`` columns, or all ``v`` where that is fewer; a
    divisor of ``v`` in whole 128-lane tiles where one of at least half
    that exists (16,384, 20,480: 1,024; 25,088: 896), else ``_TILE``
    with a ragged last tile (50,304, 32,896, 19,072). The row block: the
    largest divisor of ``n`` in whole strips, up to ``_ROWS``, whose
    block and the tile, double-buffered, fit ``_OPERAND_BYTES``; rows
    that no strip divides are one block."""
    lanes = v // 128
    cap = min(_TILE // 128, lanes)
    good = max(k for k in range(1, cap + 1) if lanes % k == 0)
    tile = 128 * (good if 2 * good >= cap else cap)
    if n % _STRIP:
        return n, tile
    rows = _STRIP
    for r in range(_STRIP, _ROWS + 1, _STRIP):
        if n % r == 0 and 4 * e * (r + tile) <= _OPERAND_BYTES:
            rows = r
    return rows, tile


def _kernel(x_ref, emb_ref, tgt_ref, lse_ref, picked_ref,
            m_ref, l_ref, p_ref, t_ref, *, v, tile, strip):
    """One (row block, vocabulary tile). ``m_ref`` / ``l_ref`` / ``p_ref``
    [rows, 128] float32: lane ``c`` of a row holds the running maximum,
    sum and picked logit of the row's columns ``c`` mod 128, so that a
    tile is folded with whole-vreg operations alone; the lanes are
    joined once, at the last tile. ``t_ref`` [rows, 128]: a row's target
    in every lane. Targets in and results out are rows ``[1, rows]``
    (lanes full in HBM), turned at the block's two ends."""
    j = pl.program_id(1)
    last = pl.num_programs(1) - 1
    rows = x_ref.shape[0]

    @pl.when(j == 0)
    def _first():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        p_ref[...] = jnp.zeros_like(p_ref)
        t_ref[...] = jnp.broadcast_to(tgt_ref[0], (128, rows)).T

    lane = lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    # the tile's columns that are the table's: fewer in a ragged last tile
    live = jnp.where(j == last, v - j * tile, tile) if v % tile else None

    def one(i, carry):
        r = pl.ds(pl.multiple_of(i * strip, strip), strip)
        s = lax.dot_general(
            x_ref[r, :], emb_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=_F32)                # [strip, tile]
        parts = [s[:, k * 128:(k + 1) * 128] for k in range(tile // 128)]
        at = t_ref[r, :] - j * tile     # the target's column in this tile
        p_ref[r, :] += sum(jnp.where(at == lane + k * 128, part, 0.0)
                           for k, part in enumerate(parts))
        if live is not None:    # what the block read past the table
            parts = [jnp.where(lane + k * 128 < live, part, _NEG_INF)
                     for k, part in enumerate(parts)]
        m_prev = m_ref[r, :]
        m_new = jnp.maximum(m_prev, functools.reduce(jnp.maximum, parts))
        l_ref[r, :] = (l_ref[r, :] * jnp.exp(m_prev - m_new)
                       + sum(jnp.exp(part - m_new) for part in parts))
        m_ref[r, :] = m_new
        return carry
    lax.fori_loop(0, rows // strip, one, None)

    @pl.when(j == last)
    def _write():
        m_lanes = m_ref[...].T                              # [128, rows]
        m = jnp.max(m_lanes, axis=0, keepdims=True)
        lse_ref[0] = m + jnp.log(jnp.sum(
            l_ref[...].T * jnp.exp(m_lanes - m), axis=0, keepdims=True))
        picked_ref[0] = jnp.sum(p_ref[...].T, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=(
    "block_rows", "tile", "strip", "interpret"))
def _ce_lse_fwd(rows, emb, targets, *, block_rows, tile, strip, interpret):
    from jax.experimental.pallas import tpu as pltpu
    n, e = rows.shape
    v = emb.shape[0]
    nb = n // block_rows
    row = pl.BlockSpec((1, 1, block_rows), lambda i, j: (i, 0, 0))
    lse, picked = pl.pallas_call(
        functools.partial(_kernel, v=v, tile=tile, strip=strip),
        grid=(nb, pl.cdiv(v, tile)),
        in_specs=[pl.BlockSpec((block_rows, e), lambda i, j: (i, 0)),
                  pl.BlockSpec((tile, e), lambda i, j: (j, 0)),
                  row],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((nb, 1, block_rows), _F32)] * 2,
        scratch_shapes=[pltpu.VMEM((block_rows, 128), _F32)] * 3
        + [pltpu.VMEM((block_rows, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(rows, emb, targets.reshape(nb, 1, block_rows))
    return lse.reshape(n), picked.reshape(n)


def ce_lse_fwd(rows, emb, targets, *, block_rows=None, tile=None,
               interpret=False):
    """(lse [N], picked [N]) in float32: a row's log-sum-exp over the
    ``V`` logits ``rows @ emb.T`` and the logit at its target (0 where
    the target is no column of the table). rows [N, E] and emb [V, E] in
    one dtype (bfloat16 in the cells), float32 accumulation; targets [N]
    int32. ``block_rows`` / ``tile``: the tests' way to a grid of several
    blocks at small shapes; left alone, ``blocks`` decides."""
    n, e = rows.shape
    v = emb.shape[0]
    if not shapes_ok(n, e, v):
        raise ValueError(f"rows [{n}, {e}] against a table of {v}: the "
                         "kernel needs whole tiles (ce_path decides)")
    auto_rows, auto_tile = blocks(n, e, v)
    block_rows = block_rows or auto_rows
    tile = min(tile or auto_tile, v)
    if n % block_rows or tile % 128:
        raise ValueError(f"blocks of {block_rows} rows by {tile} columns "
                         f"do not tile [{n}, {v}]")
    strip = _STRIP if block_rows % _STRIP == 0 else block_rows
    return _ce_lse_fwd(rows, emb, targets.astype(jnp.int32),
                       block_rows=block_rows, tile=tile, strip=strip,
                       interpret=interpret)

"""The depthwise causal convolution with its SiLU as two Pallas TPU
kernels, forward and backward, under one ``custom_vjp``.

The mathematics is ``ops/conv1d.py::causal_conv1d_silu``'s: ``y[t, c] =
silu(bias[c] + sum_j w[j, c] * x[t - (K - 1) + j, c])``, zeros before
the start. What differs is the traffic and the precision. As XLA's
fusions the forward (a padded copy of ``x``, four shifted slices of it)
ran at 2.9 times its bytes and the backward (the forward again, then
the transpose of each slice and pad) at 5.7 times (PERF.md section 6,
PR 47: 0.95 ms and 2.78 ms an array of 16,384 x 4,096 bfloat16, 56 ms
of Kimi-Linear's step). Here the forward reads ``x`` once and writes
``y`` once; the backward reads ``x`` and ``dy`` once, makes ``z``
again in VMEM and writes ``dx`` once. The taps' multiply-adds, the
bias and the SiLU are float32 from the operands' own dtype, with one
cast on the way out, where the XLA function multiplies and adds in the
compute type: no operand narrower, no term left out.

Grid (both passes): (batch, lane block, row block). A depthwise
convolution is independent a channel, so the lanes may be cut; a block
is ``[rows, lanes]`` with ``_BLOCK_BYTES`` of an operand and as many of
the row's lanes as ``_LANES`` allows (whole rows at the cells' widths:
PERF.md section 6, PR 33, short row segments move at under half the
HBM's rate). The body walks a block ``_STRIP`` rows at a time and a
strip ``_WIDTH`` lanes at a time, two loops of the kernel, so that its
code does not grow with the block.

**The rows before a row.** A row reads the ``K - 1`` rows before it. A
shift by a row is a sublane roll of a float32 value that starts one
sublane tile (``_TILE`` = 16 rows, a packed bfloat16 tile) above the
strip; inside a block that tile is the block's own, and for a block's
first strip it comes through a second view of the same operand in
blocks of ``_TILE`` rows whose index map points just before the row
block (``cca_mix.py``'s stateless halo): the forward's three grid axes
stay parallel. The tile above row 0 is zeros.

**The rows after a row.** ``dx[t] = sum_j w[j] * dz[t + (K - 1) - j]``
with ``dz = dy * silu'(z)``: computed values of the rows below. The
backward's row axis is sequential in any case (the sums below), so
they are carried, not made twice: the backward walks row blocks and
strips from the sequence's end to its start, and a strip leaves its
first ``_TILE`` rows of ``dz`` in VMEM scratch for the strip above it.
Each row of ``dx`` is written by one grid cell. ``dw[j] = sum_t dz[t]
* x[t - (K - 1) + j]`` and ``dbias = sum_t dz[t]`` are float32 sums
over the rows held in output blocks that stay in VMEM across a batch
element's row blocks, eight sublanes of partial sums a lane; the
sublanes and the batch are summed outside, as ``gated_norm.py`` sums
``scale``'s. A convolution without a bias is one with a bias of zeros
whose cotangent nobody asks for.

A last block that the rows do not fill reads past the array: the
forward's rows are causal, so the rows that exist read nothing of them
and theirs are dropped on the way out; the backward zeroes them as they
are loaded, ``x`` and ``dz`` alike.

Set-up and devices as ``gated_norm.py``: the two functions that hold
the ``pallas_call``s are jitted, so a model's layers trace and lower
each kernel once a shape; a ``pallas_call`` has no SPMD partitioning
rule, so ``causal_conv`` takes the mesh and the axes the batch is
sharded over and maps the kernels over them. Which programs get the
kernels is ``ops/conv1d.py::conv_path``'s decision.

What one v5e chip showed (PERF.md section 6, PR 55; ``scripts/
conv_timing.py``, the device's time in a profile, a call alone,
bfloat16 rows and 4 taps; forward / backward, the XLA function's
first): 16,384 x 4,096 without a bias **0.972 / 3.70 ms -> 0.466 /
0.793**, where the bytes are 0.33 / 0.49 at the HBM's rate; 8,192 x
6,144 with one 0.719 / 2.02 -> 0.349 / 0.588; 4,096 x 5,120 with one
0.302 / 0.719 -> 0.149 / 0.250. Both passes are bound by the VPU's
work, ~25 and ~45 float32 operations an element, as predicted, and
the body's step is what matters: steps of 32 / 64 / 128 / 256 rows of
128 lanes read 0.766 / 0.563 / 0.466 / 0.472 forward and 1.208 /
0.857 / 0.793 / 0.869 backward at the first shape (a step is a turn of
a loop whose operations wait on one another: too few vregs and the
turn's latency shows, too many and they spill), 64 rows of 256 lanes
0.460 / 0.827, of 512 0.495 / 0.864; blocks of 128 to 512 rows and
of 512 to 4,096 lanes within 0.01 ms: short row segments cost nothing
here, the blocks' traffic hides under the arithmetic. ``silu`` through
``0.5 + 0.5 * tanh(z / 2)`` or an approximate reciprocal reads 0.426 /
0.677: the exact division is 9% / 15% of the passes, kept, since for
large negative ``z`` neither form has ``jax.nn.sigmoid``'s relative
precision.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import program

_F32 = jnp.float32
# One operand's block. Both passes double-buffer every operand and
# result: six blocks in flight in the backward.
_BLOCK_BYTES = 2 << 20
# The most lanes of a block: whole rows at the cells' widths, and one
# strip of bfloat16 rows no more than ``_BLOCK_BYTES``.
_LANES = 8192
# Rows and lanes the body handles at a time: sixteen float32 vregs a
# value (the docstring's last paragraph has what other steps read).
_STRIP = 128
_WIDTH = 128
# One packed bfloat16 sublane tile: the rows of the view above a block,
# and the most rows a row may read before itself.
_TILE = 16
_VMEM_LIMIT = 64 << 20


def shapes_ok(c: int, taps: int) -> bool:
    """Whether the kernels tile ``c`` columns at ``taps`` taps: whole
    128-lane tiles, and the rows a row reads before itself within one
    sublane tile."""
    return c > 0 and c % 128 == 0 and 1 <= taps <= _TILE + 1


class _Static(NamedTuple):
    """What the kernels are specialised on, besides their shapes."""
    rows: int       # of a block
    lanes: int      # of a block
    strip: int
    width: int
    interpret: bool


def _blocks(t: int, c: int, itemsize: int, *, lanes=None, rows=None,
            strip=None, width=None) -> tuple[int, int, int, int]:
    """(rows, lanes, strip, width) of a block and of the body's steps:
    the most lanes under ``_LANES`` that divide ``c`` in whole tiles,
    ``_BLOCK_BYTES`` of an operand in whole strips, and no more strips
    than hold the ``t`` rows there are. What is given is taken."""
    if lanes is None:
        lanes = max(n for n in range(128, min(c, _LANES) + 1, 128)
                    if c % n == 0)
    strip = strip or _STRIP
    width = width or _WIDTH
    if rows is None:
        rows = max(_BLOCK_BYTES // (lanes * itemsize) // strip, 1) * strip
    rows = min(rows, -(-t // strip) * strip)
    if c % lanes or lanes % width or width % 128 or rows % strip or (
            strip % _TILE):
        raise ValueError(
            f"blocks of {rows} rows x {lanes} lanes in steps of {strip} x "
            f"{width} do not tile {c} columns")
    return rows, lanes, strip, width


def _each(n: int, body):
    """``body(i)`` for ``i`` in ``range(n)`` as a loop of the kernel,
    not ``n`` copies of the body."""
    lax.fori_loop(0, n, lambda i, carry: body(i) or carry, None)


def _sum8(v):
    """The rows of ``v`` summed down to eight sublanes."""
    return v.reshape(v.shape[0] // 8, 8, v.shape[1]).sum(axis=0)


def _with_tile_above(x_ref, above_ref, r0, s, start, sl, strip):
    """Rows ``[r0 - _TILE, r0 + strip)`` of the block's lanes ``sl`` in
    float32: the tile above the strip is the block's own, the view's
    for the block's first strip, zeros at the sequence's start."""
    own = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(r0 - _TILE, 0), _TILE),
                         _TILE), sl]
    above = jnp.where(s == 0, above_ref[0, :, sl], own)
    above = jnp.where(start, jnp.zeros_like(above), above)
    return jnp.concatenate([above, x_ref[0, pl.ds(r0, strip), sl]],
                           axis=0).astype(_F32)


def _taps(xe, k: int):
    """``x[t - (K - 1) + j]`` at the strip's rows ``t``, a tap ``j``,
    from the strip with its tile above."""
    return [(pltpu.roll(xe, k - 1 - j, 0) if j < k - 1 else xe)[_TILE:]
            for j in range(k)]


def _before_silu(xs, w_ref, b_ref, sl):
    z = b_ref[:, sl] + w_ref[0:1, sl] * xs[0]
    for j in range(1, len(xs)):
        z = z + w_ref[j:j + 1, sl] * xs[j]
    return z


def _steps(st: _Static, rows: int, lanes: int, body, *, last_first=False):
    """``body(s, first row of the strip, its lanes)`` over the block's
    strips (from the last one up if ``last_first``) and a strip's
    ``width``-lane slices."""
    n = rows // st.strip

    def strip(i):
        s = n - 1 - i if last_first else i
        r0 = pl.multiple_of(s * st.strip, st.strip)
        _each(lanes // st.width, lambda m: body(
            s, r0, pl.ds(pl.multiple_of(m * st.width, 128), st.width)))

    _each(n, strip)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, above_ref, w_ref, b_ref, y_ref, *, st: _Static):
    _, rows, lanes = y_ref.shape
    k = w_ref.shape[0]
    first_block = pl.program_id(2) == 0

    def step(s, r0, sl):
        xe = _with_tile_above(x_ref, above_ref, r0, s,
                              first_block & (s == 0), sl, st.strip)
        z = _before_silu(_taps(xe, k), w_ref, b_ref, sl)
        y_ref[0, pl.ds(r0, st.strip), sl] = (
            z * jax.nn.sigmoid(z)).astype(y_ref.dtype)

    _steps(st, rows, lanes, step)


def _compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _in_specs(st: _Static, k: int, block_of):
    """The specs of what both kernels read, ``block_of(i)`` the row
    block that grid cell ``i`` of a batch element's lane block works
    on."""
    tiles = st.rows // _TILE
    return [
        pl.BlockSpec((1, st.rows, st.lanes),
                     lambda b, n, i: (b, block_of(i), n)),
        pl.BlockSpec((1, _TILE, st.lanes), lambda b, n, i: (
            b, jnp.maximum(block_of(i) * tiles - 1, 0), n)),
        pl.BlockSpec((k, st.lanes), lambda b, n, i: (0, n)),
        pl.BlockSpec((1, st.lanes), lambda b, n, i: (0, n))]


@functools.partial(jax.jit, static_argnames=("st",))
def _conv_fwd(x, w, bias, *, st: _Static):
    """``y`` [B, T, C] in ``x``'s dtype. x [B, T, C]; w [K, C] and bias
    [1, C] float32. Jitted so that a model's layers share one trace and
    one Mosaic lowering."""
    b_, t, c = x.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, st=st),
        grid=(b_, c // st.lanes, pl.cdiv(t, st.rows)),
        in_specs=_in_specs(st, w.shape[0], lambda i: i),
        out_specs=pl.BlockSpec((1, st.rows, st.lanes),
                               lambda b, n, i: (b, i, n)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_compiler_params("parallel", "parallel", "parallel"),
        interpret=st.interpret,
    )(x, x, w, bias)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(x_ref, above_ref, w_ref, b_ref, dy_ref,
                dx_ref, dw_ref, db_ref, below_ref, *, st: _Static, t: int):
    """One row block, the sequence's last first. ``dw_ref`` [K, 8,
    lanes] and ``db_ref`` [8, lanes] are the batch element's lane
    block's and stay in VMEM across its row blocks; ``below_ref``
    [_TILE, lanes] holds the first rows of ``dz`` that the strip below
    left for the one above it."""
    @pl.when(pl.program_id(2) == 0)
    def _first():
        for ref in (dw_ref, db_ref, below_ref):
            ref[...] = jnp.zeros_like(ref)

    _, rows, lanes = dx_ref.shape
    k = w_ref.shape[0]
    block = pl.num_programs(2) - 1 - pl.program_id(2)
    first_block = block == 0
    ragged = t % rows != 0

    def up(v, below, by):
        """``v[t + by]`` at row ``t`` of the strip, the rows past it
        from the tile the strip below left."""
        return pltpu.roll(jnp.concatenate([v, below], axis=0),
                          st.strip + _TILE - by, 0)[:st.strip]

    def step(s, r0, sl):
        at = (0, pl.ds(r0, st.strip), sl)
        xe = _with_tile_above(x_ref, above_ref, r0, s,
                              first_block & (s == 0), sl, st.strip)
        if ragged:          # rows past the array hold anything
            live = (block * rows + r0 - _TILE + lax.broadcasted_iota(
                jnp.int32, (_TILE + st.strip, 1), 0)) < t
            xe = jnp.where(live, xe, 0.0)
        xs = _taps(xe, k)
        z = _before_silu(xs, w_ref, b_ref, sl)
        sig = jax.nn.sigmoid(z)
        dz = dy_ref[at].astype(_F32) * (sig * (1.0 + z * (1.0 - sig)))
        if ragged:
            dz = jnp.where(live[_TILE:], dz, 0.0)
        db_ref[0, :, sl] += _sum8(dz)
        for j in range(k):
            dw_ref[0, j, :, sl] += _sum8(xs[j] * dz)
        below = below_ref[:, sl]
        below_ref[:, sl] = dz[:_TILE]
        dx = w_ref[k - 1:k, sl] * dz
        for j in range(k - 1):
            dx = dx + w_ref[j:j + 1, sl] * up(dz, below, k - 1 - j)
        dx_ref[at] = dx.astype(dx_ref.dtype)

    _steps(st, rows, lanes, step, last_first=True)


@functools.partial(jax.jit, static_argnames=("st",))
def _conv_bwd(x, w, bias, dy, *, st: _Static):
    """(dx in ``x``'s dtype, dw [K, C] and dbias [1, C] float32);
    jitted for the reason ``_conv_fwd`` is."""
    b_, t, c = x.shape
    k = w.shape[0]
    blocks = pl.cdiv(t, st.rows)

    def block_of(i):
        return blocks - 1 - i

    rows_spec = pl.BlockSpec((1, st.rows, st.lanes),
                             lambda b, n, i: (b, block_of(i), n))
    dx, dw, db = pl.pallas_call(
        functools.partial(_bwd_kernel, st=st, t=t),
        grid=(b_, c // st.lanes, blocks),
        in_specs=[*_in_specs(st, k, block_of), rows_spec],
        out_specs=[rows_spec,
                   pl.BlockSpec((1, k, 8, st.lanes),
                                lambda b, n, i: (b, 0, 0, n)),
                   pl.BlockSpec((1, 8, st.lanes), lambda b, n, i: (b, 0, n))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b_, k, 8, c), _F32),
                   jax.ShapeDtypeStruct((b_, 8, c), _F32)],
        scratch_shapes=[pltpu.VMEM((_TILE, st.lanes), _F32)],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=st.interpret,
    )(x, x, w, bias, dy)
    return dx, dw.sum((0, 2)), db.sum((0, 1))[None]


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_core(x, w, bias, st: _Static):
    return _conv_fwd(x, w, bias, st=st)


def _conv_core_fwd(x, w, bias, st):
    return _conv_fwd(x, w, bias, st=st), (x, w, bias)


def _conv_core_bwd(st, res, dy):
    x = res[0]
    return _conv_bwd(*res, dy.astype(x.dtype), st=st)


_conv_core.defvjp(_conv_core_fwd, _conv_core_bwd)


def causal_conv(x, weight, bias=None, *, interpret: bool = False, mesh=None,
                batch_axes=(), **blocks):
    """``ops/conv1d.py::causal_conv1d_silu`` on the kernels: x [b, T, C];
    weight [K, C]; bias [C] or None; the same result in ``x``'s dtype,
    differentiable in all three, the cotangents in the operands'
    dtypes. ``C`` and ``K`` must pass ``shapes_ok``; ``T`` is any.
    ``blocks`` (``rows``, ``lanes``, ``strip``, ``width``) are
    ``_blocks``'s, for ``scripts/conv_timing.py`` and the tests;
    ``mesh`` and ``batch_axes`` are ``program.over_batch``'s (a sequence
    needs nothing of another's, the weights are whole on every device)."""
    _, t, c = x.shape
    k = weight.shape[0]
    if not shapes_ok(c, k):
        raise ValueError(
            f"the convolution's kernels do not tile {c} columns at {k} taps")
    core = functools.partial(_conv_core, st=_Static(
        *_blocks(t, c, x.dtype.itemsize, **blocks), interpret))
    core = program.over_batch(core, mesh, batch_axes,
                              in_specs=(0, None, None), out_specs=0)
    if bias is None:
        bias = jnp.zeros((c,), _F32)
    return core(x, weight.astype(_F32), bias.astype(_F32)[None])

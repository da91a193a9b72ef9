"""Mamba-2's gated group RMSNorm as two Pallas TPU kernels, forward and
backward, under one ``custom_vjp``.

The mathematics and the precisions are ``ops/ssm.py::
gated_group_rms_norm``'s: ``RMSNorm(y * silu(z))``, the mean square over
each of ``groups`` equal slices of the last dimension, one ``scale``
over all of it, float32 inside and ``y``'s dtype out. What differs is
the layout. The scan's kernel writes ``y`` and ``out_proj``'s matmul
reads the result as row-major ``[B, T, C]``; the XLA function's
``reshape(.., groups, C / groups)`` and mean over the last axis made
the compiler relay a float32 ``[B, T, C]`` array three times a layer
between them (PERF.md section 6, PRs 33 and 37). Here nothing leaves
that layout: each pass reads its operands once and writes its results
once.

Grid (both passes): (batch, row block). A block is whole rows
``[rows, C]`` (8 KB contiguous a row at 4,096 bfloat16 columns; PERF.md
section 6, PR 33: short row segments move at under half the HBM's
rate), ``_BLOCK_BYTES`` of an operand. The body walks a block
``_STRIP`` rows at a time, and a strip's groups as static slices of
whole 128-lane tiles, so that a strip's float32 values of one group are
a few vector registers between one load and one store and the body's
code does not grow with the block.

Backward: recomputes ``g = y * silu(z)``, ``r = rsqrt(mean(g^2) +
eps)`` and ``n = g * r`` from the saved ``y`` and ``z`` (their own
dtype: what ``jax.checkpoint`` keeps of the XLA function, no float32
residual); ``dn = dout * scale``; ``dg = r * (dn - n * mean(dn *
n))``; ``dy = dg * silu(z)``; ``dz = dg * y * silu'(z)``. ``scale``'s
cotangent is the sum over the rows of ``dout * n``: a batch's row
blocks add theirs, eight sublanes of partial sums a lane, into one
float32 block that stays in VMEM across the row-block axis (so that
axis is "arbitrary"); the sublanes and the batch are summed outside.
A last block that the rows do not fill reads past the array: its rows
are independent, their results are dropped on the way out, and they are
masked out of ``scale``'s sum.

Set-up and devices as ``ssd_scan.py``: the two functions that hold the
``pallas_call``s are jitted, so a model's layers trace and lower each
kernel once; a ``pallas_call`` has no SPMD partitioning rule, so
``gated_norm`` takes the mesh and the axes the batch is sharded over
and maps the kernels over them. Which programs get the kernels is
``ops/ssm.py::norm_path``'s decision.

What one v5e chip showed at 1 x 8,192 rows of 4,096 bfloat16 columns in
8 groups (PERF.md section 6, PR 37): timed alone in a loop the forward
0.320 ms and the backward 0.536, where a plain XLA elementwise pass
over the same bytes (two arrays in and one out; three in and two out)
takes 0.326 and 0.537, the XLA function 2.12 and 4.01 and its 0/1-matrix
form 0.55 and 1.34. Blocks of 64 to 512 rows and strips of 8 to 256
rows read 0.313-0.341 and 0.532-0.545: the kernels move bytes and
nothing else shows.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_F32 = jnp.float32
# One operand's block. Both passes double-buffer every operand and
# result: ten blocks in flight in the backward.
_BLOCK_BYTES = 2 << 20
# Rows the body handles at a time: one packed bfloat16 tile.
_STRIP = 16
_VMEM_LIMIT = 64 << 20


def shapes_ok(c: int, groups: int) -> bool:
    """Whether the kernels tile ``c`` columns in ``groups`` groups: each
    group whole 128-lane tiles."""
    return groups > 0 and c % groups == 0 and (c // groups) % 128 == 0


def _block_rows(t: int, c: int, itemsize: int) -> int:
    """Rows of a block: ``_BLOCK_BYTES`` of an operand in whole strips,
    and no more strips than hold the ``t`` rows there are."""
    rows = max(_BLOCK_BYTES // (c * itemsize) // _STRIP, 1) * _STRIP
    return min(rows, -(-t // _STRIP) * _STRIP)


def _strips(block_rows: int, body):
    """Run ``body(first row of the strip)`` over the block's strips."""
    def step(i, carry):
        body(pl.multiple_of(i * _STRIP, _STRIP))
        return carry
    lax.fori_loop(0, block_rows // _STRIP, step, None)


def _group_lanes(c: int, groups: int):
    width = c // groups
    return [slice(k * width, (k + 1) * width) for k in range(groups)]


def _gated(y_ref, z_ref, at):
    """A strip of one group in float32: ``y``, ``z``, ``sigmoid(z)``,
    ``silu(z)`` and the gated ``g = y * silu(z)``."""
    y = y_ref[at].astype(_F32)
    z = z_ref[at].astype(_F32)
    s = jax.nn.sigmoid(z)
    silu = z * s
    return y, z, s, silu, y * silu


def _factor(g, eps):
    return lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(y_ref, z_ref, scale_ref, out_ref, *, groups, eps):
    _, block_rows, c = out_ref.shape
    lanes = _group_lanes(c, groups)

    def strip(r0):
        for sl in lanes:
            at = (0, pl.ds(r0, _STRIP), sl)
            *_, g = _gated(y_ref, z_ref, at)
            out_ref[at] = (g * _factor(g, eps) * scale_ref[:, sl]
                           ).astype(out_ref.dtype)

    _strips(block_rows, strip)


def _specs(b_, t, c, rows):
    block = pl.BlockSpec((1, rows, c), lambda b, i: (b, i, 0))
    lane = pl.BlockSpec((1, c), lambda b, i: (0, 0))
    return (b_, pl.cdiv(t, rows)), block, lane


def _compiler_params(*semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit,
                   static_argnames=("groups", "eps", "rows", "interpret"))
def _norm_fwd(y, z, scale, *, groups, eps, rows, interpret):
    """The norm [B, T, C] in ``y``'s dtype. y, z [B, T, C]; scale
    [1, C] float32. Jitted so that a model's layers share one trace and
    one Mosaic lowering."""
    b_, t, c = y.shape
    grid, block, lane = _specs(b_, t, c, rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=groups, eps=eps),
        grid=grid,
        in_specs=[block, block, lane],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(y, z, scale)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(y_ref, z_ref, scale_ref, dout_ref,
                dy_ref, dz_ref, dscale_ref, *, groups, eps, t):
    """One row block. ``dscale_ref`` [8, C] is the batch's: it stays in
    VMEM across the row blocks, which add their rows' ``dout * n`` into
    it eight sublanes at a time."""
    @pl.when(pl.program_id(1) == 0)
    def _first():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    _, block_rows, c = dy_ref.shape
    lanes = _group_lanes(c, groups)
    ragged = t % block_rows != 0
    first = pl.program_id(1) * block_rows

    def strip(r0):
        if ragged:
            row = first + r0 + lax.broadcasted_iota(
                jnp.int32, (_STRIP, 1), 0)
        for sl in lanes:
            at = (0, pl.ds(r0, _STRIP), sl)
            y, z, s, silu, g = _gated(y_ref, z_ref, at)
            r = _factor(g, eps)
            n = g * r
            dout = dout_ref[at].astype(_F32)
            dn = dout * scale_ref[:, sl]
            dg = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
            dy_ref[at] = (dg * silu).astype(dy_ref.dtype)
            dz_ref[at] = (dg * y * (s * (1.0 + z * (1.0 - s)))
                          ).astype(dz_ref.dtype)
            part = dout * n
            if ragged:          # rows past the array hold anything
                part = jnp.where(row < t, part, 0.0)
            dscale_ref[0, :, sl] += sum(
                part[j:j + 8] for j in range(0, _STRIP, 8))

    _strips(block_rows, strip)


@functools.partial(jax.jit,
                   static_argnames=("groups", "eps", "rows", "interpret"))
def _norm_bwd(y, z, scale, dout, *, groups, eps, rows, interpret):
    """(dy, dz, dscale [1, C] float32); jitted for the reason
    ``_norm_fwd`` is."""
    b_, t, c = y.shape
    grid, block, lane = _specs(b_, t, c, rows)
    dy, dz, dscale = pl.pallas_call(
        functools.partial(_bwd_kernel, groups=groups, eps=eps, t=t),
        grid=grid,
        in_specs=[block, block, lane, block],
        out_specs=[block, block,
                   pl.BlockSpec((1, 8, c), lambda b, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((b_, 8, c), _F32)],
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(y, z, scale, dout)
    return dy, dz, dscale.sum((0, 1))[None]


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

class _Static(NamedTuple):
    """What the kernels are specialised on, besides their shapes."""
    groups: int
    eps: float
    rows: int       # of a block
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _norm_core(y, z, scale, static: _Static):
    return _norm_fwd(y, z, scale, **static._asdict())


def _norm_core_fwd(y, z, scale, static):
    return _norm_fwd(y, z, scale, **static._asdict()), (y, z, scale)


def _norm_core_bwd(static, res, dout):
    y = res[0]
    return _norm_bwd(*res, dout.astype(y.dtype), **static._asdict())


_norm_core.defvjp(_norm_core_fwd, _norm_core_bwd)


def gated_norm(y, z, scale, *, groups: int, eps: float,
               interpret: bool = False, mesh=None, batch_axes=()):
    """``ops/ssm.py::gated_group_rms_norm`` on the kernels: y, z
    [b, T, C]; scale [C]; the same result, differentiable in all three.
    ``C`` and ``groups`` must pass ``shapes_ok``; ``T`` is any.

    A program that spans the devices of ``mesh`` names in
    ``batch_axes`` the axes its batch is sharded over, and the kernels
    run under a ``shard_map`` over them (as ``ssd_scan.ssd_scan``): a
    row's norm needs nothing of another's, and ``scale``, held whole on
    every device, has its cotangent summed over the axes by the map's
    transpose."""
    _, t, c = y.shape
    if not shapes_ok(c, groups):
        raise ValueError(
            f"the gated norm's kernels do not tile {c} columns in "
            f"{groups} groups")
    core = functools.partial(_norm_core, static=_Static(
        groups, float(eps), _block_rows(t, c, y.dtype.itemsize), interpret))
    if batch_axes:
        from jax.sharding import PartitionSpec
        rows_spec, whole = PartitionSpec(tuple(batch_axes)), PartitionSpec()
        core = jax.shard_map(
            core, mesh=mesh, in_specs=(rows_spec, rows_spec, whole),
            out_specs=rows_spec, check_vma=False)
    return core(y, z, scale.astype(_F32)[None])

"""The two gated RMSNorms that follow a recurrence, each as two Pallas TPU
kernels, forward and backward, under one ``custom_vjp``: Mamba-2's
(``gated_norm``) and Kimi Delta Attention's output gate
(``head_gate_norm``). One grid, one block rule, one pair of kernel
shells; a strip's arithmetic (``_FORMS``) and ``scale``'s shape are what
differ.

**Mamba-2's** (``norm_gated``). The mathematics and the precisions are
``ops/gated_norm.py::gated_group_rms_norm``'s: ``RMSNorm(y * silu(z))``, the
mean square over each of ``groups`` equal slices of the last dimension,
one ``scale`` over all of it, float32 inside and ``y``'s dtype out.
What differs is the layout. The scan's kernel writes ``y`` and
``out_proj``'s matmul reads the result as row-major ``[B, T, C]``; the
XLA function's ``reshape(.., groups, C / groups)`` and mean over the
last axis made the compiler relay a float32 ``[B, T, C]`` array three
times a layer between them (PERF.md section 6, PRs 33 and 37). Here
nothing leaves that layout: each pass reads its operands once and
writes its results once.

**Kimi Delta Attention's** (``gate_normed``, PR 58):
``ops/gated_norm.py::sigmoid_gated_head_rms_norm``'s ``sigmoid(gate) *
RMSNorm_head(o) * scale``: the *normed* output gated, by a sigmoid,
where Mamba-2 norms the gated product; the mean square over each of
``heads`` equal slices (a group above), ``scale`` ``[C / heads]`` shared
by the heads, float32 inside and ``gate``'s dtype out. The same layout
and the same reason: between the recurrence's kernel and ``W_o``'s
matmul the XLA function's passes over ``[1, 16384, 4096]`` were several
times the bytes three reads and one write would cover (PERF.md section
6, PR 58).

Grid (both passes, both forms): (batch, row block). A block is whole
rows ``[rows, C]`` (8 KB contiguous a row at 4,096 bfloat16 columns;
PERF.md section 6, PR 33: short row segments move at under half the
HBM's rate), ``_BLOCK_BYTES`` of an operand. The body walks a block
``_STRIP`` rows at a time, and a strip's groups as static slices of
whole 128-lane tiles, so that a strip's float32 values of one group are
a few vector registers between one load and one store and the body's
code does not grow with the block.

Backward, Mamba-2's: recomputes ``g = y * silu(z)``, ``r =
rsqrt(mean(g^2) + eps)`` and ``n = g * r`` from the saved ``y`` and
``z`` (their own dtype: what ``jax.checkpoint`` keeps of the XLA
function, no float32 residual); ``dn = dout * scale``; ``dg = r * (dn -
n * mean(dn * n))``; ``dy = dg * silu(z)``; ``dz = dg * y * silu'(z)``;
``scale``'s cotangent is the sum over the rows of ``dout * n``. Kimi
Delta Attention's, from the saved ``o`` and ``gate`` in the same way:
``s = sigmoid(gate)``, ``r``, ``n = o * r``; ``dn = dout * s * scale``;
``do = r * (dn - n * mean(dn * n))``; ``dgate = dout * n * scale * s *
(1 - s)``; ``scale``'s cotangent the sum over the rows *and the heads*
of ``dout * s * n``. Either way a batch's row blocks add their rows'
products, eight sublanes of partial sums a lane, into one float32
``[8, C]`` block that stays in VMEM across the row-block axis (so that
axis is "arbitrary"); the sublanes, the batch and, for a ``scale``
shared by the heads, the heads are summed outside. A last block that
the rows do not fill reads past the array: its rows are independent,
their results are dropped on the way out, and they are masked out of
``scale``'s sum.

Set-up and devices as ``ssd_scan.py``: the functions that hold the
``pallas_call``s are jitted, a pair a form under names of their own
(``_norm_fwd`` / ``_norm_bwd``, ``_head_gate_fwd`` / ``_head_gate_bwd``:
what a trace's operations are called), so a model's layers trace and
lower each kernel once; a ``pallas_call`` has no SPMD partitioning
rule, so ``gated_norm`` and ``head_gate_norm`` take the mesh and the
axes the batch is sharded over and map the kernels over them. Which
programs get the kernels is ``ops/gated_norm.py::norm_path``'s decision.

What one v5e chip showed at 1 x 8,192 rows of 4,096 bfloat16 columns in
8 groups (PERF.md section 6, PR 37): timed alone in a loop the forward
0.320 ms and the backward 0.536, where a plain XLA elementwise pass
over the same bytes (two arrays in and one out; three in and two out)
takes 0.326 and 0.537, the XLA function 2.12 and 4.01 and its 0/1-matrix
form 0.55 and 1.34. Blocks of 64 to 512 rows and strips of 8 to 256
rows read 0.313-0.341 and 0.532-0.545: the kernels move bytes and
nothing else shows. PERF.md section 6, PR 58, has the output gate's
readings at 1 x 16,384 rows in 32 heads.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu.ops.pallas import program

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


def _factor(g, eps):
    return lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# a strip's arithmetic: one group's [_STRIP, width] float32 values
# ---------------------------------------------------------------------------

def _norm_gated(y, z, scale, eps):
    """Mamba-2's: ``RMSNorm(y * silu(z)) * scale``."""
    g = y * (z * jax.nn.sigmoid(z))
    return g * _factor(g, eps) * scale


def _norm_gated_grads(y, z, scale, dout, eps):
    """(dy, dz, the rows' share of dscale) of ``_norm_gated``."""
    s = jax.nn.sigmoid(z)
    silu = z * s
    g = y * silu
    r = _factor(g, eps)
    n = g * r
    dn = dout * scale
    dg = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
    return dg * silu, dg * y * (s * (1.0 + z * (1.0 - s))), dout * n


def _gate_normed(o, gate, scale, eps):
    """Kimi Delta Attention's: ``sigmoid(gate) * RMSNorm(o) * scale``."""
    return jax.nn.sigmoid(gate) * (o * _factor(o, eps) * scale)


def _gate_normed_grads(o, gate, scale, dout, eps):
    """(do, dgate, the rows' share of dscale) of ``_gate_normed``."""
    s = jax.nn.sigmoid(gate)
    r = _factor(o, eps)
    n = o * r
    ds = dout * s
    dn = ds * scale
    do = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
    return do, dout * (n * scale) * (s * (1.0 - s)), ds * n


def _silu_gate_normed(o, gate, scale, eps):
    """Gated DeltaNet's: ``silu(gate) * RMSNorm(o) * scale``."""
    return gate * jax.nn.sigmoid(gate) * (o * _factor(o, eps) * scale)


def _silu_gate_normed_grads(o, gate, scale, dout, eps):
    """(do, dgate, the rows' share of dscale) of ``_silu_gate_normed``."""
    s = jax.nn.sigmoid(gate)
    r = _factor(o, eps)
    n = o * r
    ds = dout * (gate * s)
    dn = ds * scale
    do = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
    return (do, dout * (n * scale) * (s * (1.0 + gate * (1.0 - s))),
            ds * n)


def _scale_of(scale_ref, sl, c):
    """A group's lanes of a ``scale`` over all ``c`` columns; all of one
    that the groups share."""
    return scale_ref[:, sl] if scale_ref.shape[-1] == c else scale_ref[...]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(a_ref, b_ref, scale_ref, out_ref, *, form, groups, eps):
    _, block_rows, c = out_ref.shape
    lanes = _group_lanes(c, groups)

    def strip(r0):
        for sl in lanes:
            at = (0, pl.ds(r0, _STRIP), sl)
            out_ref[at] = _FORMS[form].value(
                a_ref[at].astype(_F32), b_ref[at].astype(_F32),
                _scale_of(scale_ref, sl, c), eps).astype(out_ref.dtype)

    _strips(block_rows, strip)


def _specs(b_, t, c, rows, scale_cols):
    block = pl.BlockSpec((1, rows, c), lambda b, i: (b, i, 0))
    lane = pl.BlockSpec((1, scale_cols), lambda b, i: (0, 0))
    return (b_, pl.cdiv(t, rows)), block, lane


def _compiler_params(*semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _forward(form, a, b, scale, *, groups, eps, rows, interpret):
    """The norm [B, T, C]. a, b [B, T, C]; scale [1, C], or [1, C /
    groups] where the groups share it, float32."""
    b_, t, c = a.shape
    grid, block, lane = _specs(b_, t, c, rows, scale.shape[-1])
    out = (a, b)[_FORMS[form].out_of]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, form=form, groups=groups, eps=eps),
        grid=grid,
        in_specs=[block, block, lane],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        compiler_params=_compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(a, b, scale)


_STATIC = ("groups", "eps", "rows", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _norm_fwd(y, z, scale, **static):
    """Mamba-2's norm in ``y``'s dtype. Jitted so that a model's layers
    share one trace and one Mosaic lowering."""
    return _forward("norm_gated", y, z, scale, **static)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _head_gate_fwd(o, gate, scale, **static):
    """Kimi Delta Attention's output gate in ``gate``'s dtype; jitted
    for the reason ``_norm_fwd`` is, under a name of its own."""
    return _forward("gate_normed", o, gate, scale, **static)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _head_silu_gate_fwd(o, gate, scale, **static):
    """Gated DeltaNet's output gate in ``gate``'s dtype: ``_head_gate_fwd``
    with ``silu`` where that one has a sigmoid, under a name of its own."""
    return _forward("silu_gate_normed", o, gate, scale, **static)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(a_ref, b_ref, scale_ref, dout_ref,
                da_ref, db_ref, dscale_ref, *, form, groups, eps, t):
    """One row block. ``dscale_ref`` [8, C] is the batch's: it stays in
    VMEM across the row blocks, which add their rows' share into it
    eight sublanes at a time."""
    @pl.when(pl.program_id(1) == 0)
    def _first():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    _, block_rows, c = da_ref.shape
    lanes = _group_lanes(c, groups)
    ragged = t % block_rows != 0
    first = pl.program_id(1) * block_rows

    def strip(r0):
        if ragged:
            row = first + r0 + lax.broadcasted_iota(
                jnp.int32, (_STRIP, 1), 0)
        for sl in lanes:
            at = (0, pl.ds(r0, _STRIP), sl)
            da, db, part = _FORMS[form].grads(
                a_ref[at].astype(_F32), b_ref[at].astype(_F32),
                _scale_of(scale_ref, sl, c), dout_ref[at].astype(_F32), eps)
            da_ref[at] = da.astype(da_ref.dtype)
            db_ref[at] = db.astype(db_ref.dtype)
            if ragged:          # rows past the array hold anything
                part = jnp.where(row < t, part, 0.0)
            dscale_ref[0, :, sl] += sum(
                part[j:j + 8] for j in range(0, _STRIP, 8))

    _strips(block_rows, strip)


def _backward(form, a, b, scale, dout, *, groups, eps, rows, interpret):
    """(da, db, dscale as ``scale`` float32)."""
    b_, t, c = a.shape
    grid, block, lane = _specs(b_, t, c, rows, scale.shape[-1])
    da, db, dscale = pl.pallas_call(
        functools.partial(_bwd_kernel, form=form, groups=groups, eps=eps,
                          t=t),
        grid=grid,
        in_specs=[block, block, lane, block],
        out_specs=[block, block,
                   pl.BlockSpec((1, 8, c), lambda b, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct((b_, 8, c), _F32)],
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(a, b, scale, dout)
    dscale = dscale.sum((0, 1))
    if scale.shape[-1] != c:            # the groups share it
        dscale = dscale.reshape(groups, -1).sum(0)
    return da, db, dscale[None]


@functools.partial(jax.jit, static_argnames=_STATIC)
def _norm_bwd(y, z, scale, dout, **static):
    """(dy, dz, dscale [1, C] float32); jitted for the reason
    ``_norm_fwd`` is."""
    return _backward("norm_gated", y, z, scale, dout, **static)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _head_gate_bwd(o, gate, scale, dout, **static):
    """(do, dgate, dscale [1, C / heads] float32)."""
    return _backward("gate_normed", o, gate, scale, dout, **static)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _head_silu_gate_bwd(o, gate, scale, dout, **static):
    """(do, dgate, dscale [1, C / heads] float32)."""
    return _backward("silu_gate_normed", o, gate, scale, dout, **static)


class _Form(NamedTuple):
    value: object   # a strip's (a, b, scale, eps) -> the result
    grads: object   # (a, b, scale, dout, eps) -> (da, db, dscale's rows)
    out_of: int     # the operand whose dtype the result has
    fwd: object     # the jitted passes
    bwd: object


_FORMS = {
    "norm_gated": _Form(_norm_gated, _norm_gated_grads, 0,
                        _norm_fwd, _norm_bwd),
    "gate_normed": _Form(_gate_normed, _gate_normed_grads, 1,
                         _head_gate_fwd, _head_gate_bwd),
    "silu_gate_normed": _Form(_silu_gate_normed, _silu_gate_normed_grads, 1,
                              _head_silu_gate_fwd, _head_silu_gate_bwd)}

# the output gate's function -> the form that applies it to the normed ``o``
HEAD_GATES = {"sigmoid": "gate_normed", "silu": "silu_gate_normed"}


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

class _Static(NamedTuple):
    """What the kernels are specialised on, besides their shapes."""
    groups: int
    eps: float
    rows: int       # of a block
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm_core(a, b, scale, form: str, static: _Static):
    return _FORMS[form].fwd(a, b, scale, **static._asdict())


def _norm_core_fwd(a, b, scale, form, static):
    return (_FORMS[form].fwd(a, b, scale, **static._asdict()),
            (a, b, scale))


def _norm_core_bwd(form, static, res, dout):
    out = res[_FORMS[form].out_of]
    return _FORMS[form].bwd(*res, dout.astype(out.dtype),
                            **static._asdict())


_norm_core.defvjp(_norm_core_fwd, _norm_core_bwd)


def _apply(form, a, b, scale, groups, eps, interpret, mesh, batch_axes):
    """``form``'s kernels over a, b [b, T, C] and ``scale`` [1, C] or
    [1, C / groups], bare or over ``batch_axes`` of ``mesh``
    (``program.over_batch``): a row's norm needs nothing of another's,
    and ``scale`` is held whole on every device."""
    _, t, c = a.shape
    if not shapes_ok(c, groups):
        raise ValueError(
            f"the gated norm's kernels do not tile {c} columns in "
            f"{groups} groups")
    itemsize = max(a.dtype.itemsize, b.dtype.itemsize)
    core = functools.partial(_norm_core, form=form, static=_Static(
        groups, float(eps), _block_rows(t, c, itemsize), interpret))
    core = program.over_batch(core, mesh, batch_axes,
                              in_specs=(0, 0, None), out_specs=0)
    return core(a, b, scale.astype(_F32)[None])


def gated_norm(y, z, scale, *, groups: int, eps: float,
               interpret: bool = False, mesh=None, batch_axes=()):
    """``ops/gated_norm.py::gated_group_rms_norm`` on the kernels: y, z
    [b, T, C]; scale [C]; the same result, differentiable in all three.
    ``C`` and ``groups`` must pass ``shapes_ok``; ``T`` is any; ``mesh``
    and ``batch_axes`` are ``program.over_batch``'s."""
    return _apply("norm_gated", y, z, scale, groups, eps, interpret, mesh,
                  batch_axes)


def head_gate_norm(o, gate, scale, *, heads: int, eps: float,
                   interpret: bool = False, mesh=None, batch_axes=(),
                   gate_fn: str = "sigmoid"):
    """``ops/gated_norm.py::sigmoid_gated_head_rms_norm`` on the kernels: o,
    gate [b, T, C]; scale [C / heads], shared by the heads; the same
    result in ``gate``'s dtype, differentiable in all three. ``C`` and
    ``heads`` must pass ``shapes_ok``; ``T`` is any; ``mesh`` and
    ``batch_axes`` as ``gated_norm``'s. ``gate_fn``: ``sigmoid`` (Kimi
    Delta Attention's) or ``silu`` (Gated DeltaNet's), a kernel pair
    each (``HEAD_GATES``)."""
    return _apply(HEAD_GATES[gate_fn], o, gate, scale, heads, eps,
                  interpret, mesh, batch_axes)

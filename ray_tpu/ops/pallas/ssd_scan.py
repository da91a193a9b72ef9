"""The Mamba-2 chunked scan ("SSD", arXiv:2405.21060 section 6) as two
Pallas TPU kernels, forward and backward, under one ``custom_vjp``.

The mathematics and the precisions are ``ops/ssm.py::_ssd``'s (its
docstring states the recurrence): what differs is where the arrays
live. A chunk's ``[chunk, chunk]`` score and decay squares are made in
VMEM, a head at a time, and never reach HBM; the state is carried from
chunk to chunk in VMEM scratch; the only thing kept for the backward
beside the inputs is the state *entering* each chunk (float32, what
``_ssd`` keeps under the name ``ssm_boundary_states``), from which the
backward kernel recomputes a chunk's squares, again in VMEM. The
forward rule names its two results (``ops/remat.py::SSD_SCAN_*``): a
block recomputed under ``nn.remat`` whose policy lists them
(``models/granite.py``) finds ``y`` and the entering states kept and
does not run the forward kernel a second time; outside such a policy
(``models/nemotron_h.py``) a name is the identity.

Grid (both passes): (batch, head block, chunk), the chunk axis
"arbitrary" (sequential), walked first to last by the forward and last
to first by the backward, which carries the state's cotangent the same
way. A head block is ``_HEADS`` = 8 heads of one group: their ``dt``
rows are one float32 sublane tile, and they share the group's ``C.B^T``
score square, made once a grid cell. A group of more than eight heads
(granite-4.0-h-micro: one group of 64) is ``blocks_per_group`` head
blocks, each of which makes the group's square again and writes its
own float32 share of ``dB`` and ``dC``, summed outside the kernel; one
square a group is ROADMAP's.

Layout (PERF.md section 6, PR 29: a ``[.., H, 64]`` array is half
padding in 128-lane tiles): the kernels index ``x`` and ``y`` as
``[B, T, H*P]``, a block the ``8 * P`` lanes of its heads side by side,
``B`` and ``C`` as ``[B, T, G*N]``, a block one group's ``N`` lanes,
and ``dt`` as ``[B, H, T]``: a head's steps along the lanes, which is
the orientation the decay square's columns want; the orientation its
rows want is one ``[128, chunk]`` transpose a grid cell, of every
per-step vector of the block's heads at once. The state is ``[8 * P,
N]``, the block's heads stacked along the sublanes, so that the four
matmuls that touch it (the carried state's output, a chunk's own
state, and their two transposes each in the backward) are one matmul
for the eight heads.

Precisions: ``dt``, ``A``, the log-decays, their running sums (a
matmul against a triangle of ones at ``Precision.HIGHEST``) and every
``exp`` in float32; the decay between two steps is ``exp`` of the
*difference* of the running sums under the causal mask, never a product
of two ``exp``s; matmul operands in ``x``'s dtype with float32
accumulation.

Set-up: the two functions that hold the ``pallas_call``s are jitted, so
a model's layers, which call them at one shape, trace each kernel and
lower it to Mosaic once a trace of the step (PERF.md section 6, PR 28).

Devices: a ``pallas_call`` has no SPMD partitioning rule, so the bare
kernels are one device's. ``ssd_scan`` takes the mesh and the axes the
batch is sharded over and maps the kernels over them (``shard_map``):
the grid's batch axis is then a device's own sequences. Which programs
get that is ``ops/ssm.py::scan_path``'s decision.

What one v5e chip showed at 1 x 8,192 tokens, 64 heads of 64, state 128
in 8 groups (PERF.md section 6, PR 33): a layer's forward 1.23 ms and
backward 2.94 inside the cell's step, where ``_ssd`` took 12.6 for the
two. Timed alone in a loop (where these kernels read 1.63 + 3.25 and
``_ssd`` 3.05 + 9.58): some 0.75 ms of each pass is the blocks' traffic
(the same grid and specs round an empty body: row segments of 1 KB and
256 B move at under half the HBM's rate), and the rest grows with the
heads of a block, not with any one matmul or float32 pass over the
squares: leaving one out moved nothing. The backward is written phase
by phase over the block's heads (3.81 head by head: Mosaic schedules
close to the order it is given), the forward head by head (1.74 phase
by phase); the running sums are a matmul (seven rounds of lane rotation
and add were slower, 1.65 + 4.24: they stand at the head of everything
a grid cell does).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ray_tpu.ops.pallas import program
from ray_tpu.ops.remat import SSD_SCAN_OUT, SSD_SCAN_STATES

# Heads in a block: one float32 sublane tile of ``dt``'s [H, T] rows.
_HEADS = 8
_NEG = -1e30
_F32 = jnp.float32


def shapes_ok(h: int, p: int, g: int, n: int, chunk: int) -> bool:
    """Whether the kernels tile these shapes: the squares and the state
    on whole 128-lane tiles, a whole number of blocks of eight heads in
    each group, and eight heads side by side on whole tiles."""
    return (chunk > 0 and chunk % 128 == 0 and n > 0 and n % 128 == 0
            and g > 0 and h % g == 0 and (h // g) % _HEADS == 0
            and (_HEADS * p) % 128 == 0)


def blocks_per_group(h: int, g: int) -> int:
    """The head blocks of one group: how many grid cells make the
    group's ``C.B^T`` score square a chunk (1 at eight heads a group)."""
    return h // g // _HEADS


# ---------------------------------------------------------------------------
# what both kernels make first
# ---------------------------------------------------------------------------

def _dot(a, b, dims, **kw):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=_F32, **kw)


_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b.T
_TN = ((0,), (0,))      # a.T @ b


def _triangle(n, keep):
    rows = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return keep(rows, cols)


def _running_sums(dt_ref, a_ref):
    """``dt`` [8, L], the running sums of ``dt * A`` inside the chunk
    [8, L] and their last column [8, 1]: a head a row."""
    dt = dt_ref[0]
    n = dt.shape[1]
    ones = _triangle(n, lambda u, s: u <= s).astype(_F32)
    cum = _dot(dt * a_ref[...], ones, _NN, precision=lax.Precision.HIGHEST)
    return dt, cum, cum[:, n - 1:]


def _as_columns(*rows):
    """Row vectors [8, L] each -> [L, 128]: vector ``i``'s head ``j`` is
    column ``8 * i + j``. One transpose of whole tiles."""
    n = rows[0].shape[1]
    pad = jnp.zeros((128 - _HEADS * len(rows), n), _F32)
    return jnp.concatenate([*rows, pad], axis=0).T


def _column(cols, i, j):
    k = _HEADS * i + j
    return cols[:, k:k + 1]                              # [L, 1]


def _head_lanes(p):
    """The lanes of each of the block's heads in ``[.., 8 * P]``."""
    return [slice(j * p, (j + 1) * p) for j in range(_HEADS)]


def _kept(total, lanes):
    """What a chunk keeps of the state entering it, ``exp`` of the
    chunk's whole log-decay: [8, 1] -> [8, lanes], a head a row. Spread
    over the lanes before the ``exp`` because Mosaic broadcasts a
    [1, 1] along one axis at a time: the product that uses a head's
    row spreads it over the sublanes."""
    return jnp.exp(jnp.broadcast_to(total, (total.shape[0], lanes)))


def _weight(scores, causal, cum_col, cum_row, dt_row):
    """One head's decay square and ``scores * decay``: [L, L] float32,
    ``t`` down the rows and ``s <= t`` along the lanes."""
    decay = jnp.exp(jnp.where(causal, cum_col - cum_row, _NEG))
    held = scores * decay
    return decay, held, held * dt_row


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, dt_ref, a_ref, skip_ref, b_ref, c_ref,
                y_ref, enter_ref, state_ref, xs_ref, *, p):
    @pl.when(pl.program_id(2) == 0)
    def _first():
        state_ref[...] = jnp.zeros_like(state_ref)

    dtype = x_ref.dtype
    dt, cum, total = _running_sums(dt_ref, a_ref)
    n = dt.shape[1]
    cols = _as_columns(cum, jnp.exp(cum), jnp.exp(total - cum) * dt)
    bm, cm = b_ref[0], c_ref[0]                          # [L, N]
    scores = _dot(cm, bm, _NT)                           # [L, L]
    causal = _triangle(n, lambda t, s: t >= s)
    state = state_ref[...]                               # [8P, N]
    enter_ref[...] = state
    carried = _dot(cm, state.astype(dtype), _NT)         # [L, 8P]
    lanes = _head_lanes(p)
    for j, sl in enumerate(lanes):
        x = x_ref[0, :, sl]
        xf = x.astype(_F32)
        _, _, weight = _weight(scores, causal, _column(cols, 0, j),
                               cum[j:j + 1], dt[j:j + 1])
        y = (_dot(weight.astype(dtype), x, _NN)
             + carried[:, sl] * _column(cols, 1, j))
        y_ref[0, :, sl] = (y + skip_ref[:, sl] * xf).astype(y_ref.dtype)
        xs_ref[:, sl] = (xf * _column(cols, 2, j)).astype(dtype)
    own = _dot(xs_ref[...], bm, _TN)                     # [8P, N]
    keep = _kept(total, state.shape[1])                  # [8, N]
    for j, sl in enumerate(lanes):
        state_ref[sl] = keep[j:j + 1] * state[sl] + own[sl]


def _specs(b_, hp, gn, p, n, chunk, nc, *, backward):
    """The block specs both passes share, and the grid. ``backward``
    walks the chunks last to first."""
    width = _HEADS * p
    blocks = hp // width
    per_group = blocks // (gn // n)

    def chunk_of(c):
        return nc - 1 - c if backward else c

    rows = pl.BlockSpec((1, chunk, width),
                        lambda b, h, c: (b, chunk_of(c), h))
    steps = pl.BlockSpec((1, _HEADS, chunk),
                         lambda b, h, c: (b, h, chunk_of(c)))
    rate = pl.BlockSpec((_HEADS, 1), lambda b, h, c: (h, 0))
    skip = pl.BlockSpec((1, width), lambda b, h, c: (0, h))
    group = pl.BlockSpec((1, chunk, n),
                         lambda b, h, c: (b, chunk_of(c), h // per_group))
    enter = pl.BlockSpec((None, None, None, width, n),
                         lambda b, h, c: (b, chunk_of(c), h, 0, 0))
    return (b_, blocks, nc), per_group, rows, steps, rate, skip, group, enter


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


@functools.partial(jax.jit, static_argnames=("p", "n", "chunk", "interpret"))
def _ssd_fwd(x, dt, rate, skip, bm, cm, *, p, n, chunk, interpret):
    """(y [B, T, H*P], the state entering each chunk [B, T/chunk,
    H/8, 8P, N] float32). x [B, T, H*P]; dt [B, H, T] float32; rate
    (``A``) [H, 1]; skip (``D``, a lane each) [1, H*P]; bm, cm [B, T,
    G*N]. Jitted so that a model's layers share one trace and one
    Mosaic lowering."""
    b_, t, hp = x.shape
    nc = t // chunk
    width = _HEADS * p
    grid, _, rows, steps, rate_s, skip_s, group, enter = _specs(
        b_, hp, bm.shape[2], p, n, chunk, nc, backward=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        grid=grid,
        in_specs=[rows, steps, rate_s, skip_s, group, group],
        out_specs=[rows, enter],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b_, nc, hp // width, width, n),
                                        _F32)],
        scratch_shapes=[_vmem((width, n), _F32),
                        _vmem((chunk, width), x.dtype)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(x, dt, rate, skip, bm, cm)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(x_ref, dt_ref, a_ref, skip_ref, b_ref, c_ref, enter_ref,
                dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dskip_ref,
                dstate_ref, xs_ref, dz_ref, *, p):
    """One chunk of one head block, the chunks walked last to first.
    ``dstate_ref`` carries the cotangent of the state *leaving* the
    chunk. Writes the cotangents of ``x``, ``dt`` (with its part
    through ``dt * A``), of the log-decays ``dt * A`` themselves
    (``da``: ``A``'s is their sum against ``dt``, taken outside), of
    ``B`` and ``C`` (summed over the block's heads here) and the
    chunk's share of the skip's, a lane each."""
    @pl.when(pl.program_id(2) == 0)
    def _first():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    dtype = x_ref.dtype
    dt, cum, total = _running_sums(dt_ref, a_ref)
    n = dt.shape[1]
    to_end = jnp.exp(total - cum)
    cols = _as_columns(cum, jnp.exp(cum), to_end, to_end * dt)
    bm, cm = b_ref[0], c_ref[0]
    scores = _dot(cm, bm, _NT)
    causal = _triangle(n, lambda t, s: t >= s)
    state = enter_ref[...]                               # [8P, N]
    state_lo = state.astype(dtype)
    dstate = dstate_ref[...]
    dstate_lo = dstate.astype(dtype)
    carried = _dot(cm, state_lo, _NT)                    # [L, 8P]
    dxs = _dot(bm, dstate_lo, _NT)                       # [L, 8P]
    keep = _kept(total, state.shape[1])                  # [8, N]

    # Phase by phase over the block's heads, not head by head: Mosaic
    # schedules close to the order it is given (PERF.md section 6, PR
    # 31), and a head alone is one chain of square -> matmul -> sums.
    heads = range(_HEADS)
    lanes = _head_lanes(p)
    x = [x_ref[0, :, sl] for sl in lanes]
    dy = [dy_ref[0, :, sl] for sl in lanes]
    dt_row = [dt[j:j + 1] for j in heads]
    squares = [_weight(scores, causal, _column(cols, 0, j), cum[j:j + 1],
                       dt_row[j]) for j in heads]
    decay, held, weight = zip(*squares)
    weight = [w.astype(dtype) for w in weight]
    dweight = [_dot(dy[j], x[j], _NT) for j in heads]    # [L, L]
    dx = [_dot(weight[j], dy[j], _TN) for j in heads]    # [L, P]
    dscores = sum(dweight[j] * (decay[j] * dt_row[j]) for j in heads)
    # d weight . weight / dt_s, and its sums down and along
    through = [dweight[j] * held[j] for j in heads]
    down = [jnp.sum(t, axis=0, keepdims=True) for t in through]  # [1, L]
    along = [jnp.sum(through[j] * dt_row[j], axis=1, keepdims=True)
             for j in heads]                                     # [L, 1]
    xf = [z.astype(_F32) for z in x]
    dyf = [z.astype(_F32) for z in dy]
    e_col, end_col, r_col = ([_column(cols, i, j) for j in heads]
                             for i in (1, 2, 3))
    for j, sl in enumerate(lanes):
        dx_ref[0, :, sl] = (dx[j] + dxs[:, sl] * r_col[j]
                            + skip_ref[:, sl] * dyf[j]).astype(dx_ref.dtype)
        dskip_ref[:, sl] = jnp.sum(dyf[j] * xf[j], axis=0, keepdims=True)
        dz_ref[:, sl] = (dyf[j] * e_col[j]).astype(dtype)
        xs_ref[:, sl] = (xf[j] * r_col[j]).astype(dtype)
    # the cotangents that come out a step a row of a column
    de = [jnp.sum(dyf[j] * carried[:, sl], axis=1, keepdims=True)
          for j, sl in enumerate(lanes)]
    dr = [jnp.sum(dxs[:, sl] * xf[j], axis=1, keepdims=True)
          for j, sl in enumerate(lanes)]
    lane = lax.broadcasted_iota(jnp.int32, (n, 128), 1)
    back = jnp.zeros((n, 128), _F32)     # columns on their way to rows
    for j in heads:
        back = jnp.where(lane == j, along[j] + de[j] * e_col[j]
                         - dr[j] * r_col[j], back)
        back = jnp.where(lane == _HEADS + j, dr[j] * end_col[j], back)
    dcum_rows = [-down[j] * dt_row[j] for j in heads]
    dtotal = [
        jnp.sum(dr[j] * r_col[j], axis=0, keepdims=True)
        + keep[j:j + 1, :1] * jnp.sum(
            jnp.sum(dstate[sl] * state[sl], axis=1, keepdims=True),
            axis=0, keepdims=True)
        for j, sl in enumerate(lanes)]
    dscores_lo = dscores.astype(dtype)
    dz, xs = dz_ref[...], xs_ref[...]
    dc_ref[0] = (_dot(dscores_lo, bm, _NN) + _dot(dz, state_lo, _NN)
                 ).astype(dc_ref.dtype)
    db_ref[0] = (_dot(dscores_lo, cm, _TN) + _dot(xs, dstate_lo, _NN)
                 ).astype(db_ref.dtype)
    into = _dot(dz, cm, _TN)                             # [8P, N]
    for j, sl in enumerate(lanes):
        dstate_ref[sl] = keep[j:j + 1] * dstate[sl] + into[sl]

    back = back.T                                        # [128, L]
    last = lax.broadcasted_iota(jnp.int32, (_HEADS, n), 1) == n - 1
    dcum = (back[:_HEADS] + jnp.concatenate(dcum_rows, axis=0)
            + jnp.where(last, jnp.concatenate(dtotal, axis=0), 0.0))
    ones = _triangle(n, lambda u, t: u >= t).astype(_F32)
    da = _dot(dcum, ones, _NN, precision=lax.Precision.HIGHEST)
    da_ref[0] = da
    ddt_ref[0] = (back[_HEADS:2 * _HEADS]
                  + jnp.concatenate(down, axis=0) + da * a_ref[...])


@functools.partial(jax.jit, static_argnames=("p", "n", "chunk", "interpret"))
def _ssd_bwd(x, dt, rate, skip, bm, cm, entering, dy, *, p, n, chunk,
             interpret):
    """(dx, ddt [B, H, T], da [B, H, T], dB, dC, dskip [1, H*P]);
    jitted for the reason ``_ssd_fwd`` is."""
    b_, t, hp = x.shape
    gn = bm.shape[2]
    nc = t // chunk
    width = _HEADS * p
    grid, per_group, rows, steps, rate_s, skip_s, group, enter = _specs(
        b_, hp, gn, p, n, chunk, nc, backward=True)
    # a group's head blocks each write their own share of dB and dC
    share = pl.BlockSpec(
        (None, 1, chunk, n),
        lambda b, h, c: (h % per_group, b, nc - 1 - c, h // per_group))
    part = jax.ShapeDtypeStruct(
        (per_group, b_, t, gn), bm.dtype if per_group == 1 else _F32)
    lanes = pl.BlockSpec((None, None, 1, width),
                         lambda b, h, c: (b, nc - 1 - c, 0, h))
    dx, ddt, da, db, dc, dskip = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=grid,
        in_specs=[rows, steps, rate_s, skip_s, group, group, enter, rows],
        out_specs=[rows, steps, steps, share, share, lanes],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   part, part,
                   jax.ShapeDtypeStruct((b_, nc, 1, hp), _F32)],
        scratch_shapes=[_vmem((width, n), _F32),
                        _vmem((chunk, width), x.dtype),
                        _vmem((chunk, width), x.dtype)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(x, dt, rate, skip, bm, cm, entering, dy)
    return (dx, ddt, da, db.sum(0).astype(bm.dtype),
            dc.sum(0).astype(cm.dtype), dskip.sum((0, 1)))


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

class _Static(NamedTuple):
    """What the kernels are specialised on, besides their shapes."""
    p: int          # head width
    n: int          # state width
    chunk: int
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_core(x, dt, rate, skip, bm, cm, static: _Static):
    return _ssd_fwd(x, dt, rate, skip, bm, cm, **static._asdict())[0]


def _ssd_core_fwd(x, dt, rate, skip, bm, cm, static):
    y, entering = _ssd_fwd(x, dt, rate, skip, bm, cm, **static._asdict())
    # both named before they part into primal and residuals (the trap
    # ``ops/remat.py::name_core_results`` records)
    y, entering = checkpoint_name(y, SSD_SCAN_OUT), checkpoint_name(
        entering, SSD_SCAN_STATES)
    return y, (x, dt, rate, skip, bm, cm, entering)


def _ssd_core_bwd(static, res, dy):
    x, dt, rate = res[:3]
    dx, ddt, da, db, dc, dskip = _ssd_bwd(*res, dy.astype(x.dtype),
                                          **static._asdict())
    drate = jnp.sum(da * dt, axis=(0, 2))[:, None].astype(rate.dtype)
    return dx, ddt, drate, dskip, db, dc


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128,
             interpret: bool = False, mesh=None, batch_axes=()):
    """``ops/ssm.py::mamba2_scan`` on the kernels: the same arguments
    (x [b, T, H, P]; dt [b, T, H]; A, D [H]; B, C [b, T, G, N]), the
    same result, differentiable in all six. The shapes must pass
    ``shapes_ok``; ``T`` need not be a multiple of ``chunk`` (the tail
    is padded with steps that neither decay nor write the state).
    ``mesh`` and ``batch_axes`` are ``program.over_batch``'s: a
    sequence's scan needs nothing of another's, and ``A`` and ``D`` are
    held whole on every device."""
    b_, t, h, p = x.shape
    g, n = B.shape[2:]
    if not shapes_ok(h, p, g, n, chunk):
        raise ValueError(
            f"the scan's kernels do not tile heads {h} x {p}, state "
            f"{g} x {n}, chunk {chunk}")
    pad = (-t) % chunk

    def rows(z):
        z = z.reshape(b_, t, -1)
        return jnp.pad(z, ((0, 0), (0, pad), (0, 0))) if pad else z

    # The shards cross the boundary as the kernels index them, heads
    # merged (``ops/attention.py`` says why).
    core = program.over_batch(
        functools.partial(_ssd_core, static=_Static(p, n, chunk, interpret)),
        mesh, batch_axes, in_specs=(0, 0, None, None, 0, 0), out_specs=0)
    y = core(
        rows(x), jnp.swapaxes(rows(dt.astype(_F32)), 1, 2),
        A.astype(_F32)[:, None], jnp.repeat(D.astype(_F32), p)[None],
        rows(B), rows(C))
    return y[:, :t].reshape(b_, t, h, p)

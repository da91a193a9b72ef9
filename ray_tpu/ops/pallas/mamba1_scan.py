"""The Mamba-1 selective scan (arXiv:2312.00752 section 3) as two Pallas
TPU kernels, forward and backward, under one ``custom_vjp``.

The recurrence and the precisions are ``ops/mamba1.py::mamba1_scan``'s (its
docstring states them): what differs is where the arrays live. The
``[N, C]`` state stands in VMEM scratch from the first row block of a
sequence to the last; a block reads its rows of ``x, dt, B, C`` once and
writes ``y`` once; the only thing kept for the backward beside the inputs
is the state *entering* each block (float32: ``[T / rows, N, C]``, 21 MB
a layer at 4,096 rows of 5,120 channels in blocks of 64, where the XLA
path's chunks of 4 keep 335 MB), from which the backward kernel recomputes
a block's states, again into VMEM. The forward rule names its two results
(``ops/remat.py::MAMBA1_SCAN_*``), as ``ssd_scan.py``'s does: a block
recomputed under ``nn.remat`` whose policy lists them
(``models/phi4flash.py``) finds ``y`` and the entering states kept and does
not run the forward kernel a second time; outside such a policy a name is
the identity.

The decay ``exp(dt_t[c] A[n, c])`` differs by channel *and* by state, so
there is no matmul in it: every operation is elementwise on the VPU (and
the EUP, for the ``exp``). Hence the layout, which is this file's one
idea: **the channels fill a vreg, sublanes and lanes both** (``C`` as
``[C / 1024, 8, 128]``: 1,024 channels a vreg), and the state index is a
leading dimension (a channel group's state is ``[N, 8, 128]``: ``N``
vregs). Then ``B_t[n]`` and ``C_t[n]``, one number for all channels, are
one number for all of a vreg, the sum over the states is ``N`` plain vreg
additions (no reduction inside a vreg), and ``x_t, dt_t`` are dense vregs
(no spread along sublanes). ``[.., N, C]`` with the states on the
sublanes, as the XLA path lays it out, would want ``B_t`` and ``C_t``
spread over a vreg's lanes a row and a sublane reduction a row for ``y``.

A grid cell's two ends bring a block into that layout and back, in VMEM:

- HBM has the rows the other way, ``[T, C]`` tiled eight rows by 128
  channels, and a reshape that splits the lanes is a real copy there
  (XLA's took 0.26 ms an array a call, 5.8 ms of the step's first 18.9:
  PERF.md section 6). So the kernels read and write ``[rows, C]`` blocks
  as they are and re-tile them (``_gather``, ``_scatter``: eight ``[8,
  128]`` tiles stacked and their two leading axes exchanged, on the
  otherwise idle XLU), casting ``x`` up on the way in.
- ``B`` and ``C`` come as a block's ``rows * N`` scalars in SMEM and are
  spread a vreg each into ``[rows, N, 8, 128]`` scratch (``_spread``),
  once a grid cell for all its channel groups. A row's work on its ``N``
  states is then a handful of operations on ``[N, 8, 128]`` arrays, not
  ``N`` times as many on vregs with a scalar each: that is what set-up
  wanted (a kernel body's price in the benchmark's worker is per equation:
  3,900 of them cost ``step.trace_lower_s`` 9.5 s, 1,450 cost it ~3), at
  0.19 ms a forward call and 0.11 a backward one for the spreading.

Grid (both passes): (batch, row block), the row blocks "arbitrary"
(sequential), walked first to last by the forward and last to first by
the backward, which carries the state's cotangent the same way. Inside a
grid cell the channel groups are the outer loop and the block's rows the
inner one, so that a group's ``N`` state vregs (16 of the 64) stay in
registers down the rows.

The backward, a channel group at a time: recompute the block's states
from the one that entered it into a ``[rows + 1, N, 8, 128]`` scratch,
then walk the rows in reverse with the ``N`` vregs of the state's
cotangent in registers. ``dx`` and ``d dt`` are sums over the states (vreg
additions) and are written a row; ``dA`` and ``dD`` are sums over the rows
and accumulate in their output blocks, resident in VMEM all along a
sequence. ``dB_t[n]`` and ``dC_t[n]`` are sums over *all* channels of a
row: each (row, state) has a vreg of partial sums in scratch that the
channel groups add into, the block's last act reduces those over their
sublanes, and the lanes' sum is left to XLA (``[T, N, 128]`` float32:
33.5 MB a layer each). At 64 rows a block the backward holds 38 MB of
VMEM; 128 rows want 77 and are refused.

Precisions: float32 everywhere, as ``xla_chunked``: state, decays,
writes, ``exp``, accumulations, cotangents. ``x`` is cast up as a block
is re-tiled, ``B, C`` by the caller (``mamba1_scan`` below) before the
kernels see them, and their cotangents are cast back after. No
``dot_general`` in either kernel. The sums run in another order than the
XLA path's (a sequential walk where it has a tree).

Set-up: the two functions that hold the ``pallas_call``s are jitted, so a
model's layers, which call them at one shape, trace each kernel and lower
it to Mosaic once a trace of the step (PERF.md section 6, PR 28). Every
loop is a ``fori_loop``; two rows are written out a turn (``_down_rows``).

Devices: a ``pallas_call`` has no SPMD partitioning rule, so the kernels
are one device's; ``ops/mamba1.py::mamba1_path`` gives them one-device
programs alone.

What one v5e chip showed is in PERF.md section 6 (PR 49).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ray_tpu.ops.remat import MAMBA1_SCAN_OUT, MAMBA1_SCAN_STATES

# Channels in a vreg: 8 sublanes of 128 lanes.
_SUB, _LANES = 8, 128
GROUP = _SUB * _LANES
# Rows a block: the backward's scratch is ``(5 * rows + 1) * N`` vregs and
# five blocks re-tiled.
ROWS = 64
_F32 = jnp.float32


def shapes_ok(channels: int, states: int) -> bool:
    """Whether the kernels tile these shapes: the channels whole vregs
    (8 sublanes of 128 lanes) and the states whole 8-sublane tiles (the
    rows of ``dB`` and ``dC`` a block writes)."""
    return (channels > 0 and channels % GROUP == 0
            and states > 0 and states % _SUB == 0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _down_rows(rows, unroll, row, carry):
    """``row(i, carry) -> carry`` for ``i`` in 0 .. ``rows`` - 1, ``unroll``
    of them a turn of the loop, written out (Mosaic's ``fori_loop`` unrolls
    all of a loop or none of it)."""
    def turn(j, carry):
        for u in range(unroll):
            carry = row(j * unroll + u, carry)
        return carry

    return lax.fori_loop(0, rows // unroll, turn, carry)


def _over_states(v):
    """``[N, 8, 128] -> [8, 128]``, the sum over the states by halves: a
    chain four additions long for sixteen, where adding them in turn makes
    one of sixteen that every row would wait out."""
    while v.shape[0] > 1:
        half = v.shape[0] // 2
        odd = v[2 * half:]
        v = v[:half] + v[half:2 * half]
        if odd.shape[0]:
            v = jnp.concatenate([v, odd])
    return v[0]


def _octets(dense_ref, move):
    """``move(t0, g)`` for every eight rows from ``t0`` on of every channel
    group ``g`` of a block ``[rows, G, 8, 128]``."""
    rows, groups = dense_ref.shape[:2]

    def octet(j, _):
        t0 = pl.multiple_of((j // groups) * _SUB, _SUB)
        move(t0, j % groups)

    lax.fori_loop(0, rows // _SUB * groups, octet, None)


def _lanes_of(g, s):
    return pl.ds(pl.multiple_of((g * _SUB + s) * _LANES, _LANES), _LANES)


def _gather(rows_ref, dense_ref):
    """A block's rows as HBM has them, ``[1, rows, C]`` (a tile eight rows
    by 128 channels), into ``[rows, G, 8, 128]`` (a tile one row's 1,024
    channels), cast up: eight tiles a time, their rows and their order
    exchanged."""
    def move(t0, g):
        tiles = [rows_ref[0, pl.ds(t0, _SUB), _lanes_of(g, s)].astype(_F32)
                 for s in range(_SUB)]
        dense_ref[pl.ds(t0, _SUB), g] = jnp.swapaxes(jnp.stack(tiles), 0, 1)

    _octets(dense_ref, move)


def _scatter(dense_ref, rows_ref):
    """``_gather`` backwards, for what a kernel writes."""
    def move(t0, g):
        tiles = jnp.swapaxes(dense_ref[pl.ds(t0, _SUB), g], 0, 1)
        for s in range(_SUB):
            rows_ref[0, pl.ds(t0, _SUB), _lanes_of(g, s)] = tiles[s]

    _octets(dense_ref, move)


def _spread(scalars_ref, vregs_ref):
    """A block's ``B`` or ``C``, ``rows * N`` scalars in SMEM, each over a
    vreg of its own in ``[rows, N, 8, 128]``: made once a grid cell and
    read by every channel group, so that a row's states are one array."""
    rows, n = vregs_ref.shape[:2]

    def row(t, _):
        for k in range(n):
            vregs_ref[t, k] = jnp.full((_SUB, _LANES),
                                       scalars_ref[0, t * n + k], _F32)

    lax.fori_loop(0, rows, row, None)


def _fwd_kernel(b_ref, c_ref, x_rows, dt_rows, a_ref, d_ref,
                y_rows, enter_ref,
                state_ref, x_ref, dt_ref, y_ref, bs_ref, cs_ref, *, unroll):
    """One row block. ``state_ref`` ``[G, N, 8, 128]`` carries the state;
    the other scratch is the block re-tiled (``x, dt, y`` ``[rows, G, 8,
    128]``) and its scalars spread (``B, C`` ``[rows, N, 8, 128]``)."""
    @pl.when(pl.program_id(1) == 0)
    def _first():
        state_ref[...] = jnp.zeros_like(state_ref)

    enter_ref[0, 0] = state_ref[...]
    _gather(x_rows, x_ref)
    _gather(dt_rows, dt_ref)
    _spread(b_ref, bs_ref)
    _spread(c_ref, cs_ref)
    rows, groups = x_ref.shape[:2]

    def group(g, _):
        def row(t, h):
            x, dt = x_ref[t, g], dt_ref[t, g]
            h = jnp.exp(dt * a_ref[g]) * h + (dt * x) * bs_ref[t]
            y_ref[t, g] = _over_states(h * cs_ref[t]) + d_ref[g] * x
            return h

        state_ref[g] = _down_rows(rows, unroll, row, state_ref[g])

    lax.fori_loop(0, groups, group, None)
    _scatter(y_ref, y_rows)


def _specs(b_, nb, groups, rows, n, *, backward):
    """The block specs both passes share. ``backward`` walks the row
    blocks last to first."""
    from jax.experimental.pallas import tpu as pltpu

    def block_of(i):
        return nb - 1 - i if backward else i

    scalars = pl.BlockSpec((None, 1, rows * n),
                           lambda b, i: (b * nb + block_of(i), 0, 0),
                           memory_space=pltpu.SMEM)
    rows_s = pl.BlockSpec((1, rows, groups * GROUP),
                          lambda b, i: (b, block_of(i), 0))
    rates = pl.BlockSpec((groups, n, _SUB, _LANES), lambda b, i: (0, 0, 0, 0))
    skip = pl.BlockSpec((groups, _SUB, _LANES), lambda b, i: (0, 0, 0))
    enter = pl.BlockSpec((1, 1, groups, n, _SUB, _LANES),
                         lambda b, i: (b, block_of(i), 0, 0, 0, 0))
    return scalars, rows_s, rates, skip, enter


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, _F32)


class _Static(NamedTuple):
    """What the kernels are specialised on, besides their shapes."""
    rows: int
    unroll: int
    interpret: bool


@functools.partial(jax.jit, static_argnames=("rows", "unroll", "interpret"))
def _mamba1_fwd(bm, cm, x, dt, rates, skip, *, rows, unroll, interpret):
    """(y [B, T, C], the state entering each row block [B, T / rows, G,
    N, 8, 128]), both float32. bm, cm [B * T / rows, 1, rows * N] float32
    (a block's scalars, a row after a row); x [B, T, C] in any dtype; dt
    [B, T, C] float32; rates (``A``) [G, N, 8, 128]; skip (``D``) [G, 8,
    128]. Jitted so that a model's layers share one trace and one Mosaic
    lowering."""
    b_, t, _ = x.shape
    groups, n = rates.shape[:2]
    nb = t // rows
    scalars, rows_s, rates_s, skip_s, enter = _specs(
        b_, nb, groups, rows, n, backward=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, unroll=unroll),
        grid=(b_, nb),
        in_specs=[scalars, scalars, rows_s, rows_s, rates_s, skip_s],
        out_specs=[rows_s, enter],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((b_, nb) + rates.shape, _F32)],
        scratch_shapes=[_vmem(rates.shape)]
        + [_vmem((rows, groups, _SUB, _LANES))] * 3
        + [_vmem((rows, n, _SUB, _LANES))] * 2,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(bm, cm, x, dt, rates, skip)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(b_ref, c_ref, x_rows, dt_rows, a_ref, d_ref, enter_ref,
                dy_rows,
                dx_rows, ddt_rows, db_ref, dc_ref, da_ref, dd_ref,
                dstate_ref, states_ref, accb_ref, accc_ref,
                x_ref, dt_ref, dy_ref, dx_ref, ddt_ref, bs_ref, cs_ref,
                *, unroll):
    """One row block, the blocks walked last to first. ``dstate_ref``
    carries the cotangent of the state *leaving* the block;
    ``states_ref[t + 1]`` is the state row ``t`` left (``[0]`` the one
    that entered the block), one channel group's at a time; ``accb_ref``
    and ``accc_ref`` hold a vreg of partial sums for each (row, state);
    the other scratch is the forward's."""
    @pl.when(pl.program_id(1) == 0)
    def _first():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    _gather(x_rows, x_ref)
    _gather(dt_rows, dt_ref)
    _gather(dy_rows, dy_ref)
    _spread(b_ref, bs_ref)
    _spread(c_ref, cs_ref)
    accb_ref[...] = jnp.zeros_like(accb_ref)
    accc_ref[...] = jnp.zeros_like(accc_ref)
    rows, groups = x_ref.shape[:2]

    def group(g, _):
        states_ref[0] = enter_ref[0, 0, g]

        def again(t, h):
            x, dt = x_ref[t, g], dt_ref[t, g]
            h = jnp.exp(dt * a_ref[g]) * h + (dt * x) * bs_ref[t]
            states_ref[t + 1] = h
            return h

        _down_rows(rows, unroll, again, states_ref[0])

        def back(i, carry):
            dh, dskip = carry
            t = rows - 1 - i
            x, dt, dy = x_ref[t, g], dt_ref[t, g], dy_ref[t, g]
            rates = a_ref[g]
            decay = jnp.exp(dt * rates)
            dh = dh + dy * cs_ref[t]
            accc_ref[t] += states_ref[t + 1] * dy
            accb_ref[t] += dh * (dt * x)
            # d(log decay) = dh . h_{t-1} . decay
            dlog = dh * states_ref[t] * decay
            da_ref[0, g] += dlog * dt
            # sum_n d(write) . B, and sum_n d(log decay) . A
            through_w = _over_states(dh * bs_ref[t])
            ddt_ref[t, g] = _over_states(dlog * rates) + through_w * x
            dx_ref[t, g] = through_w * dt + d_ref[g] * dy
            return decay * dh, dskip + dy * x

        dstate_ref[g], dskip = _down_rows(
            rows, unroll, back,
            (dstate_ref[g], jnp.zeros((_SUB, _LANES), _F32)))
        dd_ref[0, g] += dskip

    lax.fori_loop(0, groups, group, None)
    _scatter(dx_ref, dx_rows)
    _scatter(ddt_ref, ddt_rows)

    def reduce(t, _):
        for k in range(accb_ref.shape[1]):
            db_ref[0, t, k:k + 1] = jnp.sum(accb_ref[t, k], axis=0,
                                            keepdims=True)
            dc_ref[0, t, k:k + 1] = jnp.sum(accc_ref[t, k], axis=0,
                                            keepdims=True)

    lax.fori_loop(0, rows, reduce, None)


@functools.partial(jax.jit, static_argnames=("rows", "unroll", "interpret"))
def _mamba1_bwd(bm, cm, x, dt, rates, skip, entering, dy, *, rows, unroll,
                interpret):
    """(dx, ddt [B, T, C] float32; dB, dC [B, T, N, 128], their lanes
    still to be summed; dA [B, G, N, 8, 128] and dD [B, G, 8, 128], a
    sequence each); dy [B, T, C] float32; jitted for the reason
    ``_mamba1_fwd`` is."""
    b_, t, _ = x.shape
    groups, n = rates.shape[:2]
    nb = t // rows
    scalars, rows_s, rates_s, skip_s, enter = _specs(
        b_, nb, groups, rows, n, backward=True)
    lanes = pl.BlockSpec((1, rows, n, _LANES),
                         lambda b, i: (b, nb - 1 - i, 0, 0))
    whole = jax.ShapeDtypeStruct(x.shape, _F32)
    part = jax.ShapeDtypeStruct((b_, t, n, _LANES), _F32)
    drates = pl.BlockSpec((1, groups, n, _SUB, _LANES),
                          lambda b, i: (b, 0, 0, 0, 0))
    dskip = pl.BlockSpec((1, groups, _SUB, _LANES), lambda b, i: (b, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, unroll=unroll),
        grid=(b_, nb),
        in_specs=[scalars, scalars, rows_s, rows_s, rates_s, skip_s, enter,
                  rows_s],
        out_specs=[rows_s, rows_s, lanes, lanes, drates, dskip],
        out_shape=[whole, whole, part, part,
                   jax.ShapeDtypeStruct((b_,) + rates.shape, _F32),
                   jax.ShapeDtypeStruct((b_,) + skip.shape, _F32)],
        scratch_shapes=[_vmem(rates.shape),
                        _vmem((rows + 1, n, _SUB, _LANES)),
                        _vmem((rows, n, _SUB, _LANES)),
                        _vmem((rows, n, _SUB, _LANES))]
        + [_vmem((rows, groups, _SUB, _LANES))] * 5
        + [_vmem((rows, n, _SUB, _LANES))] * 2,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(bm, cm, x, dt, rates, skip, entering, dy)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _mamba1_core(bm, cm, x, dt, rates, skip, static: _Static):
    return _mamba1_fwd(bm, cm, x, dt, rates, skip, **static._asdict())[0]


def _mamba1_core_fwd(bm, cm, x, dt, rates, skip, static):
    y, entering = _mamba1_fwd(bm, cm, x, dt, rates, skip, **static._asdict())
    # both named before they part into primal and residuals (the trap
    # ``ops/remat.py::name_core_results`` records)
    y, entering = checkpoint_name(y, MAMBA1_SCAN_OUT), checkpoint_name(
        entering, MAMBA1_SCAN_STATES)
    return y, (bm, cm, x, dt, rates, skip, entering)


def _mamba1_core_bwd(static, res, dy):
    bm, _, x = res[:3]
    dx, ddt, db, dc, da, dd = _mamba1_bwd(*res, dy, **static._asdict())
    db, dc = (z.sum(-1).reshape(bm.shape) for z in (db, dc))
    return db, dc, dx.astype(x.dtype), ddt, da.sum(0), dd.sum(0)


_mamba1_core.defvjp(_mamba1_core_fwd, _mamba1_core_bwd)


def mamba1_scan(x, dt, A, B, C, D, *, rows: int = ROWS, unroll: int = 2,
                interpret: bool = False):
    """``ops/mamba1.py::mamba1_scan`` on the kernels: the same arguments (x
    [b, T, C] any dtype; dt [b, T, C]; A [C, N]; B, C [b, T, N]; D [C]),
    the same result ``y`` [b, T, C] float32, differentiable in all six,
    each cotangent in its argument's dtype. The shapes must pass
    ``shapes_ok``; ``T`` need not be whole blocks of ``rows`` (the tail
    is padded with rows that neither decay nor write the state)."""
    b_, t, c = x.shape
    n = A.shape[1]
    if not shapes_ok(c, n):
        raise ValueError(f"the scan's kernels do not tile {c} channels by "
                         f"{n} states")
    if rows % _SUB or rows % unroll:
        raise ValueError(f"blocks of {rows} rows are not whole tiles of "
                         f"{_SUB} rows and turns of {unroll}")
    pad = (-t) % rows
    nb = (t + pad) // rows

    def padded(z):
        return jnp.pad(z, ((0, 0), (0, pad), (0, 0))) if pad else z

    def vregs(z):       # [.., C] -> [C / 1024, .., 8, 128]
        z = z.astype(_F32).reshape(*z.shape[:-1], c // GROUP, _SUB, _LANES)
        return jnp.moveaxis(z, -3, 0)

    def scalars(z):     # [b, T, N] -> a block's rows after one another
        return padded(z.astype(_F32)).reshape(b_ * nb, 1, rows * n)

    y = _mamba1_core(scalars(B), scalars(C), padded(x),
                     padded(dt.astype(_F32)), vregs(A.T), vregs(D),
                     _Static(rows, unroll, interpret))
    return y[:, :t]

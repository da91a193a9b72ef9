"""CCA's passes between its projections and the flash kernel as two
Pallas TPU kernels, forward and backward, under one ``custom_vjp``.

The mathematics is ``ops/cca.py::_qk_for_kernel``'s (its docstring has
the equations): from the compressed ``[q~ | k~]`` ``[B, T, (H + G) * D]``
the depthwise causal convolution ``conv0``, the causal convolution
within heads ``conv1``, the q-k mean, the L2 norm with the keys'
temperature and the partial rotation, to ``q`` ``[B, T, H * D]`` and
``k`` ``[B, T, G * D]``. What differs is the layout and the traffic. As
XLA's fusions the passes laid ``[B, T, 1280]`` out with ``T`` minor-most
round the batched dot, copied into and out of that layout and kept
float32 arrays between a hundred operations a layer (PERF.md section 5,
PR 38: 49.5 ms a step for 1.28 ms of bytes). Here nothing leaves the
row-major ``[B, T, lanes]`` that ``qkv``'s matmul writes and the flash
kernel indexes: the forward reads ``[q~ | k~]`` once and writes ``q``
and ``k`` once; the backward reads ``[q~ | k~]`` and the two cotangents
once, makes the forward's values again in VMEM and writes ``d[q~ | k~]``
once.

Grid (both passes): (batch, row block), a block whole rows ``[rows,
(H + G) * D]``. The body walks a block ``_STRIP`` rows at a time and a
strip's heads as ``D``-lane slices (``D`` whole 128-lane tiles) in two
loops of the kernel, over the groups and over a group's query heads, so
that the body holds one query head and one key head, not ten heads:
``conv0`` on the VPU in float32; ``conv1`` one ``[strip, D] x [D, D]``
product a tap a head on the MXU; the mean of a group (a key head with
its ``H / G`` query heads), the norm's reduction over a head's lanes,
``tau`` and the rotation (lane ``i`` with ``i + rotary / 2``, two lane
rolls and a select) in float32; one cast on the way out. Cos and sin
come as two ``[T, D]`` float32 tables (``rope_tables``: ones and zeros
on the lanes that do not turn), made outside from the model's one
``angles`` and so once a step, not once a layer.

**The rows before a row.** A row reads the ``K0 + K1 - 2`` rows before
it (2 at ZAYA's taps of 2 and 2). A shift by a row is a sublane roll of
a float32 value that starts one sublane tile (``_TILE`` = 16 rows)
above the strip; inside a block that tile is the block's own, and for a
block's first strip it comes through a **second view of the same
operand** in blocks of ``_TILE`` rows whose index map points just
before the row block. A view and not a carry in scratch, because it is
stateless: the forward's two grid axes stay parallel, and the backward,
which walks the row blocks from the last to the first, reads the same
view where a carry would run the wrong way. A sequence's first rows
have zeros before them **for each convolution's own input**: the tile
above row 0 is zeroed as ``[q~ | k~]`` and again as ``conv0``'s result
(``shift_rows`` pads after ``conv0``: ``conv1``'s row ``-1`` is zero,
not ``conv0``'s bias); a block's first rows inside a sequence are not.

**The rows after a row.** ``d[q~ | k~]`` at row ``t`` takes from the
outputs at ``t .. t + K0 + K1 - 2``. Those are computed values (the
norm's cotangent through ``conv1``'s transposed products), so they are
**carried**, not read again: the backward walks row blocks and strips
from the sequence's end to its start, and a strip leaves the first
``_TILE`` rows of each shifted tap's product and of ``conv0``'s
cotangent in VMEM scratch for the strip above it. Each row of ``d[q~ |
k~]`` is written by one grid cell and no row's forward is made twice.
The cotangents of ``conv0``'s and ``conv1``'s weights and biases and of
``tau`` (1.3 MB of float32 for ``conv1``'s ``[2, 10, 128, 128]``) are
summed in output blocks that stay in VMEM across a batch element's row
blocks (eight sublanes of partial sums a lane for the vectors) and are
summed over the batch outside, as ``gated_norm.py`` sums ``scale``'s.

**Precisions** are the XLA path's or higher. As there: ``conv0`` in
float32, its result rounded to the compute type as the MXU's operand
(that rounding is the XLA path's ``astype`` between the convolutions);
``conv1`` with float32 sums; the mean, the norm, ``tau`` and the
rotation in float32; one cast out. Higher: ``conv1``'s result stays
float32 (the XLA function rounds it to the compute type between HBM
arrays; here it never leaves VMEM); ``d[q~ | k~]`` is summed in float32
over its four sources and cast once (autodiff casts each and sums in
the compute type); ``conv1``'s weights' cotangent is returned in
float32 (autodiff rounds it to the compute type first). No operand is
narrower than the XLA path's, no term left out.

A last block that the rows do not fill reads past the array: the
forward's rows are causal, so the rows that exist read nothing of them
and theirs are dropped on the way out; the backward zeroes them as they
are loaded, ``[q~ | k~]`` and the cotangents alike.

Set-up and devices as ``gated_norm.py``: the two functions that hold
the ``pallas_call``s are jitted, so a model's layers trace and lower
each kernel once; a ``pallas_call`` has no SPMD partitioning rule, so
``cca_mix`` takes the mesh and the axes the batch is sharded over and
maps the kernels over them. Which programs get the kernels is
``ops/cca.py::cca_path``'s decision.

What one v5e chip showed at 2 x 8,192 rows of 1,280 bfloat16 lanes,
taps of 2 and 2 (PERF.md section 6, PR 39; device time of the custom
call in a profile, a call alone): the forward 0.30 ms and the backward
0.45 where the XLA passes take 3.17 and 5.79 (the backward with its
second forward) and the bytes 0.06 and 0.09 at the HBM's rate: bound
by the body's arithmetic, as predicted. The strip is what matters, the
block hardly: strips of 128 / 256 / 512 rows read 0.46 / 0.28 / 0.30
forward and 0.82 / 0.63 / 0.45 backward (a ``[128, 128]`` weight is
loaded into the MXU once a product, whatever the rows that stream past
it), blocks of 512 and 1,024 rows at one strip size within 0.01 ms.
**The loops over the heads are there for set-up, and cost time**: with
the ten heads unrolled the same bodies read 0.18 and 0.37 (1.0 ms less
a step over five layers), but their ~2,100 equations were ~4 s of
tracing and ~3.5 s a lowering in the benchmark's worker, and the step
is lowered twice: warm ``setup_s`` +8.7% against a bound of 10%. As
loops the bodies are ~540 equations.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import program

_F32 = jnp.float32
# Rows of a block: 1.3 MB of ``[q~ | k~]``; the backward double-buffers
# three such operands and one result beside 2.7 MB of the weights'
# cotangents.
_BLOCK_ROWS = 512
# Rows the body handles at a time, the M of the MXU's products: a whole
# block (the docstring's last paragraph has what shorter strips cost).
_STRIP = 512
# One packed bfloat16 sublane tile: the rows of the view above a block,
# and the most rows a row may read before itself.
_TILE = 16
_VMEM_LIMIT = 64 << 20
_EPS = 1e-6             # ``ops/cca.py::l2_normalise``'s

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def halo_rows(taps) -> int:
    """The rows before itself that a row reads."""
    return sum(taps) - 2


def shapes_ok(c: int, n_head: int, n_kv_head: int, taps) -> bool:
    """Whether the kernels tile ``c`` lanes of ``n_head + n_kv_head``
    heads at these taps: each head whole 128-lane tiles, whole groups,
    and the rows a row reads before itself within one sublane tile."""
    heads = n_head + n_kv_head
    return (n_kv_head > 0 and n_head % n_kv_head == 0 and c % heads == 0
            and (c // heads) % 128 == 0 and min(taps) >= 1
            and halo_rows(taps) <= _TILE)


def block_rows(t: int) -> int:
    """Rows of a block: ``_BLOCK_ROWS`` in whole strips, and no more
    strips than hold the ``t`` rows there are."""
    return min(_BLOCK_ROWS, -(-t // _STRIP) * _STRIP)


def rope_tables(angles, t: int, d: int):
    """(cos, sin) ``[t, d]`` float32 for ``partial_rope``'s rotation in
    halves written as ``x * cos + partner(x) * sin``: ``cos`` twice,
    then ones; ``-sin``, ``sin``, then zeros."""
    half = angles.shape[-1]
    a = angles[:t].astype(_F32)
    cos, sin = jnp.cos(a), jnp.sin(a)
    rest = (t, d - 2 * half)
    return (jnp.concatenate([cos, cos, jnp.ones(rest, _F32)], axis=-1),
            jnp.concatenate([-sin, sin, jnp.zeros(rest, _F32)], axis=-1))


class _Static(NamedTuple):
    """What the kernels are specialised on, besides their shapes."""
    n_head: int
    n_kv_head: int
    half: int       # of the lanes that turn
    rows: int       # of a block
    strip: int
    interpret: bool


# ---------------------------------------------------------------------------
# what both kernels make first
# ---------------------------------------------------------------------------

def _shift(v, by: int):
    """``v[t - by]`` at row ``t``; the first ``by`` rows wrap."""
    return pltpu.roll(v, by, 0) if by else v


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


def _convs(xe, start, w0_ref, b0_ref, w1_ref, b1_ref, sl, head, dtype):
    """Both convolutions of one head on a strip with its tile above.
    Returns ``conv0``'s shifted inputs (tile included), ``conv1``'s
    shifted operands and its float32 result (the strip's rows)."""
    k0, k1 = w0_ref.shape[0], w1_ref.shape[0]
    xs = [_shift(xe, k0 - 1 - j) for j in range(k0)]
    y = b0_ref[:, sl] + sum(w0_ref[j:j + 1, sl] * xs[j] for j in range(k0))
    # conv1's input before the sequence is zero, not conv0's bias
    y = jnp.concatenate(
        [jnp.where(start, jnp.zeros_like(y[:_TILE]), y[:_TILE]), y[_TILE:]],
        axis=0)
    ys = [_shift(y, k1 - 1 - j)[_TILE:].astype(dtype) for j in range(k1)]
    c = b1_ref[:, sl] + sum(
        jnp.dot(ys[j], w1_ref[j, head], preferred_element_type=_F32)
        for j in range(k1))
    return xs, ys, c


def _inverse_norm(z):
    return lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + _EPS)


def _partner(v, half: int):
    """Lane ``i + half`` at lane ``i < half``, lane ``i - half`` at the
    ``half`` lanes after; the other lanes hold what the tables zero."""
    d = v.shape[-1]
    lane = lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return jnp.where(lane < half, pltpu.roll(v, d - half, 1),
                     pltpu.roll(v, half, 1))


def _lanes_of(d: int):
    """``lanes(i)``: head ``i``'s ``d`` lanes, ``i`` a loop's index."""
    return lambda i: pl.ds(pl.multiple_of(i * d, 128), d)


def _each(n: int, body):
    """``body(i)`` for ``i`` in ``range(n)`` as a loop of the kernel,
    not ``n`` copies of the body: the bodies are what set-up pays for."""
    lax.fori_loop(0, n, lambda i, carry: body(i) or carry, None)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, above_ref, w0_ref, b0_ref, w1_ref, b1_ref, tau_ref,
                cos_ref, sin_ref, q_ref, k_ref, sum_ref, *, st: _Static):
    """One row block. ``sum_ref`` [strip, D] float32 sums a group's
    query heads' inputs for its key head's mean."""
    h, g = st.n_head, st.n_kv_head
    rep, d = h // g, k_ref.shape[-1] // g
    lanes = _lanes_of(d)
    first_block = pl.program_id(1) == 0
    scale = math.sqrt(d)

    def strip(s):
        r0 = pl.multiple_of(s * st.strip, st.strip)
        rows = pl.ds(r0, st.strip)
        start = first_block & (s == 0)

        def head(i):
            xe = _with_tile_above(x_ref, above_ref, r0, s, start, lanes(i),
                                  st.strip)
            c = _convs(xe, start, w0_ref, b0_ref, w1_ref, b1_ref, lanes(i),
                       i, x_ref.dtype)[2]
            return xe[_TILE:], c

        def out(z, tau=None):
            n = z * (scale * _inverse_norm(z))
            if tau is not None:
                n = n * tau
            return n * cos_ref[rows] + _partner(n, st.half) * sin_ref[rows]

        def group(grp):
            half_k = 0.5 * x_ref[0, rows, lanes(h + grp)].astype(_F32)
            sum_ref[...] = jnp.zeros_like(sum_ref)

            def query_head(r):
                i = grp * rep + r
                x, c = head(i)
                sum_ref[...] += x
                q_ref[0, rows, lanes(i)] = out(
                    c + (0.5 * x + half_k)).astype(q_ref.dtype)

            _each(rep, query_head)
            _, c = head(h + grp)
            k_ref[0, rows, lanes(grp)] = out(
                c + (half_k + (0.5 / rep) * sum_ref[...]),
                tau_ref[:, lanes(grp)]).astype(k_ref.dtype)

        _each(g, group)

    _each(st.rows // st.strip, strip)


def _compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _in_specs(st: _Static, c: int, d: int, k0: int, k1: int, block_of):
    """The specs of what both kernels read, ``block_of(i)`` the row
    block that grid cell ``i`` of a batch element works on."""
    heads = st.n_head + st.n_kv_head
    tiles = st.rows // _TILE

    def whole(*shape):
        return pl.BlockSpec(shape, lambda b, i: (0,) * len(shape))

    table = pl.BlockSpec((st.rows, d), lambda b, i: (block_of(i), 0))
    return [
        pl.BlockSpec((1, st.rows, c), lambda b, i: (b, block_of(i), 0)),
        pl.BlockSpec((1, _TILE, c), lambda b, i: (
            b, jnp.maximum(block_of(i) * tiles - 1, 0), 0)),
        whole(k0, c), whole(1, c), whole(k1, heads, d, d), whole(1, c),
        whole(1, st.n_kv_head * d), table, table]


@functools.partial(jax.jit, static_argnames=("st",))
def _mix_fwd(qk, w0, b0, w1, b1, tau, cos, sin, *, st: _Static):
    """(q [B, T, H*D], k [B, T, G*D]) in ``qk``'s dtype. Jitted so that
    a model's layers share one trace and one Mosaic lowering."""
    b_, t, c = qk.shape
    d = c // (st.n_head + st.n_kv_head)
    widths = (st.n_head * d, st.n_kv_head * d)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, st=st),
        grid=(b_, pl.cdiv(t, st.rows)),
        in_specs=_in_specs(st, c, d, w0.shape[0], w1.shape[0], lambda i: i),
        out_specs=[pl.BlockSpec((1, st.rows, w), lambda b, i: (b, i, 0))
                   for w in widths],
        out_shape=[jax.ShapeDtypeStruct((b_, t, w), qk.dtype)
                   for w in widths],
        scratch_shapes=[pltpu.VMEM((st.strip, d), _F32)],
        compiler_params=_compiler_params("parallel", "parallel"),
        interpret=st.interpret,
    )(qk, qk, w0, b0, w1.astype(qk.dtype), b1, tau, cos, sin)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(x_ref, above_ref, w0_ref, b0_ref, w1_ref, b1_ref, tau_ref,
                cos_ref, sin_ref, dq_ref, dk_ref,
                dx_ref, dw0_ref, db0_ref, dw1_ref, db1_ref, dtau_ref,
                g_below, y_below, sum_ref, *, st: _Static, t: int):
    """One row block, the sequence's last first. The five sums are the
    batch element's and stay in VMEM across its row blocks; ``g_below``
    [K1, _TILE, C] and ``y_below`` [_TILE, C] hold what the strip below
    left for the one above it; ``sum_ref`` [strip, D] sums a group's
    query heads: their inputs, then the cotangents of their norms'
    inputs."""
    @pl.when(pl.program_id(1) == 0)
    def _first():
        for ref in (dw0_ref, db0_ref, dw1_ref, db1_ref, dtau_ref,
                    g_below, y_below):
            ref[...] = jnp.zeros_like(ref)

    h, g = st.n_head, st.n_kv_head
    rep, d = h // g, dk_ref.shape[-1] // g
    lanes = _lanes_of(d)
    k0, k1 = w0_ref.shape[0], w1_ref.shape[0]
    block = pl.num_programs(1) - 1 - pl.program_id(1)
    first_block = block == 0
    scale = math.sqrt(d)
    n_strips = st.rows // st.strip
    ragged = t % st.rows != 0
    dtype = x_ref.dtype

    def up(v, below, by):
        """``v[t + by]`` at row ``t`` of the strip, the rows past it
        from the tile the strip below left."""
        return pltpu.roll(jnp.concatenate([v, below], axis=0),
                          st.strip + _TILE - by, 0)[:st.strip]

    def strip(n):
        s = n_strips - 1 - n
        r0 = pl.multiple_of(s * st.strip, st.strip)
        rows = pl.ds(r0, st.strip)
        start = first_block & (s == 0)
        if ragged:          # rows past the array hold anything
            live = (block * st.rows + r0 - _TILE + lax.broadcasted_iota(
                jnp.int32, (_TILE + st.strip, 1), 0)) < t

        def pull(i, other, do_ref, do_sl, tau_sl=None):
            """Head ``i``, whose mean is half its own input and
            ``other``: (its input, the cotangent of the norm's input,
            the convolutions' part of its input's cotangent)."""
            sl = lanes(i)
            xe = _with_tile_above(x_ref, above_ref, r0, s, start, sl,
                                  st.strip)
            if ragged:
                xe = jnp.where(live, xe, 0.0)
            xs, ys, c = _convs(xe, start, w0_ref, b0_ref, w1_ref, b1_ref,
                               sl, i, dtype)
            x = xe[_TILE:]
            z = c + (0.5 * x + other)
            r = _inverse_norm(z)
            u = z * r
            # the rotation's transpose is the turn back
            do = do_ref[0, rows, do_sl].astype(_F32)
            dn = do * cos_ref[rows] - _partner(do, st.half) * sin_ref[rows]
            if ragged:      # the cotangents' and the tables' rows too
                dn = jnp.where(live[_TILE:], dn, 0.0)
            if tau_sl is not None:
                dtau_ref[0, :, tau_sl] += scale * _sum8(dn * u)
                dn = dn * tau_ref[:, tau_sl]
            dz = (scale * r) * (
                dn - u * jnp.sum(dn * u, axis=-1, keepdims=True))
            db1_ref[0, :, sl] += _sum8(dz)
            dc = dz.astype(dtype)
            dy = None
            for j in range(k1):
                dw1_ref[0, j, i] += lax.dot_general(
                    ys[j], dc, _TN, preferred_element_type=_F32)
                gj = lax.dot_general(dc, w1_ref[j, i], _NT,
                                     preferred_element_type=_F32)
                if j < k1 - 1:
                    below = g_below[j, :, sl]
                    g_below[j, :, sl] = gj[:_TILE]
                    gj = up(gj, below, k1 - 1 - j)
                dy = gj if dy is None else dy + gj
            db0_ref[0, :, sl] += _sum8(dy)
            below = y_below[:, sl]
            y_below[:, sl] = dy[:_TILE]
            dx = None
            for j in range(k0):
                dw0_ref[0, j, :, sl] += _sum8(xs[j][_TILE:] * dy)
                term = w0_ref[j:j + 1, sl] * (
                    up(dy, below, k0 - 1 - j) if j < k0 - 1 else dy)
                dx = term if dx is None else dx + term
            return x, dz, dx

        def group(grp):
            def add_input(r):
                sum_ref[...] += x_ref[0, rows, lanes(grp * rep + r)].astype(
                    _F32)

            sum_ref[...] = jnp.zeros_like(sum_ref)
            _each(rep, add_input)
            q_sum = sum_ref[...]
            if ragged:
                q_sum = jnp.where(live[_TILE:], q_sum, 0.0)
            x, dz, dx = pull(h + grp, (0.5 / rep) * q_sum, dk_ref,
                             lanes(grp), lanes(grp))
            half_k, to_q, dx_k = 0.5 * x, (0.5 / rep) * dz, dx + 0.5 * dz

            def query_head(r):
                i = grp * rep + r
                _, dz, dx = pull(i, half_k, dq_ref, lanes(i))
                sum_ref[...] += dz
                dx_ref[0, rows, lanes(i)] = (
                    dx + (0.5 * dz + to_q)).astype(dx_ref.dtype)

            sum_ref[...] = jnp.zeros_like(sum_ref)
            _each(rep, query_head)
            dx_ref[0, rows, lanes(h + grp)] = (
                dx_k + 0.5 * sum_ref[...]).astype(dx_ref.dtype)

        _each(g, group)

    _each(n_strips, strip)


@functools.partial(jax.jit, static_argnames=("st",))
def _mix_bwd(qk, w0, b0, w1, b1, tau, cos, sin, dq, dk, *, st: _Static):
    """(dqk, dw0, db0, dw1, db1, dtau lanes): ``dqk`` in ``qk``'s
    dtype, the rest float32 in the operands' shapes; jitted for the
    reason ``_mix_fwd`` is."""
    b_, t, c = qk.shape
    heads = st.n_head + st.n_kv_head
    d = c // heads
    k0, k1 = w0.shape[0], w1.shape[0]
    blocks = pl.cdiv(t, st.rows)

    def block_of(i):
        return blocks - 1 - i

    def rows_of(width):
        return pl.BlockSpec((1, st.rows, width),
                            lambda b, i: (b, block_of(i), 0))

    def sums(*shape):
        return (pl.BlockSpec((1, *shape), lambda b, i: (b,) + (0,) * len(
            shape)), jax.ShapeDtypeStruct((b_, *shape), _F32))

    outs = [(rows_of(c), jax.ShapeDtypeStruct(qk.shape, qk.dtype)),
            sums(k0, 8, c), sums(8, c), sums(k1, heads, d, d), sums(8, c),
            sums(8, st.n_kv_head * d)]
    dqk, dw0, db0, dw1, db1, dtau = pl.pallas_call(
        functools.partial(_bwd_kernel, st=st, t=t),
        grid=(b_, blocks),
        in_specs=[*_in_specs(st, c, d, k0, k1, block_of),
                  rows_of(st.n_head * d), rows_of(st.n_kv_head * d)],
        out_specs=[spec for spec, _ in outs],
        out_shape=[shape for _, shape in outs],
        scratch_shapes=[pltpu.VMEM((k1, _TILE, c), _F32),
                        pltpu.VMEM((_TILE, c), _F32),
                        pltpu.VMEM((st.strip, d), _F32)],
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=st.interpret,
    )(qk, qk, w0, b0, w1.astype(qk.dtype), b1, tau, cos, sin, dq, dk)
    return (dqk, dw0.sum((0, 2)), db0.sum((0, 1))[None], dw1.sum(0),
            db1.sum((0, 1))[None], dtau.sum((0, 1))[None])


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _mix_core(qk, w0, b0, w1, b1, tau, cos, sin, st: _Static):
    return tuple(_mix_fwd(qk, w0, b0, w1, b1, tau, cos, sin, st=st))


def _mix_core_fwd(*args):
    return tuple(_mix_fwd(*args[:-1], st=args[-1])), args[:-1]


def _mix_core_bwd(st, res, g):
    qk = res[0]      # the scope is the forward's: ``mix``
    return (*_mix_bwd(*res, *(z.astype(qk.dtype) for z in g), st=st),
            None, None)


_mix_core.defvjp(_mix_core_fwd, _mix_core_bwd)


def cca_mix(qk, conv0, conv1, tau, angles, *, n_head: int, n_kv_head: int,
            interpret: bool = False, mesh=None, batch_axes=()):
    """``ops/cca.py::_qk_for_kernel`` on the kernels: the same operands,
    (q [B, T, H, D], k [B, T, G, D]) in ``qk``'s dtype, differentiable
    in ``qk``, both convolutions' weights and biases and ``tau``. The
    shapes must pass ``shapes_ok``; ``T`` is any; ``mesh`` and
    ``batch_axes`` are ``program.over_batch``'s (a sequence needs
    nothing of another's, the weights are whole on every device)."""
    b_, t, c = qk.shape
    (w0, b0), (w1, b1) = conv0, conv1
    taps = (w0.shape[0], w1.shape[0])
    d = c // (n_head + n_kv_head)
    half = angles.shape[-1]
    if not shapes_ok(c, n_head, n_kv_head, taps) or 2 * half > d:
        raise ValueError(
            f"CCA's kernels do not tile {c} lanes of {n_head} + "
            f"{n_kv_head} heads at taps {taps}, {2 * half} lanes turning")
    core = functools.partial(_mix_core, st=_Static(
        n_head, n_kv_head, half, block_rows(t), _STRIP, interpret))
    core = program.over_batch(core, mesh, batch_axes,
                              in_specs=(0,) + (None,) * 7, out_specs=(0, 0))
    f32 = functools.partial(jnp.asarray, dtype=_F32)
    with jax.named_scope("mix"):
        q, k = core(qk, f32(w0), f32(b0)[None], f32(w1), f32(b1)[None],
                    jnp.repeat(f32(tau), d)[None], *rope_tables(angles, t, d))
    return q.reshape(b_, t, n_head, d), k.reshape(b_, t, n_kv_head, d)

"""Kimi Delta Attention's chunked gated delta rule as two Pallas TPU
kernels, forward and backward, under one ``custom_vjp``.

The mathematics is ``ops/kda.py``'s (its docstring has the recurrence
and the chunked form's five equations; chunk 64): what differs is where
a chunk's arrays live. The running sums ``G``, the scaled copies of the
keys and queries, ``A``, ``B``, ``T = (I + Diag(beta) A)^-1``, ``W``,
``Uv`` and ``U`` are made in VMEM and never reach HBM; the ``[K, V]``
state is carried from chunk to chunk in VMEM scratch along a sequential
grid axis, as ``ssd_scan.py`` carries Mamba's. HBM sees ``q, k, v, g,
beta`` in and ``o`` out, and, under differentiation, the state that
entered each chunk (float32 ``[T / 64, H, V, K]``), from which the
backward kernel recomputes a chunk's squares, again in VMEM. The
forward rule names its two results (``ops/remat.py::KDA_SCAN_*``). A
caller under a plain ``jax.checkpoint`` makes both again in its
backward pass, and the states are alive only inside that one mixer's
backward; a recomputed block whose policy keeps the two names
(``models/kimi_linear.py``) holds them from its first pass to its
backward, 0.8 GB a layer at 16,384 rows of 32 heads, and the forward
kernel runs once a step.

**``q`` and ``k`` come as the mixer's convolutions left them**
(``normalize_qk``, which ``ops/kda.py`` sets for the model): the
un-normalised rows in the projections' dtype, bfloat16 in the
Kimi-Linear cell. A cell casts a head's square to float32 as it reads
it and makes ``unit(q) * 128^-1/2`` and ``unit(k)`` there (``_unit``:
``x * rsqrt(sum_lanes(x * x) + 1e-6)``, one more lane reduce beside
``B``'s diagonal); the backward kernel carries its ``dq, dk`` back
through the same map (``_unit_back``: ``r * (d - u * sum_lanes(d *
u))``) and writes the cotangents of the rows as they came, in their
dtype. No float32 copy of ``q`` or ``k`` and no cotangent of one
reaches HBM, and the ``custom_vjp`` keeps the rows as they came (134 MB
each a layer at 16,384 x 4,096 bfloat16 where the unit float32 copies
were 268). Without the flag ``q`` and ``k`` are taken for the scaled
queries and unit keys themselves, float32: the tests' way in.

**A grid cell is two chunks of a block of heads** (``_ROWS`` = 128
rows; ``heads_a_cell``: four heads, or two, or one). With keys and
values 128 wide every array a head makes is then one ``[128, 128]``
float32 square, and every square over rows (``A``, ``B``, ``T``) is
block-diagonal, a ``[64, 64]`` block a chunk: the two chunks share each
of those matmuls and differ only where the state passes from the first
to the second. A head's work is one chain of dependent matmuls; the
kernels are written phase by phase over the cell's heads (``_each``),
so that the chains stand side by side in the order Mosaic schedules by.
Grid (both passes): (batch, block of heads, pair of chunks), the last
axis "arbitrary" (sequential), first to last in the forward and last to
first in the backward, which carries the state's cotangent the same
way.

**The scores by halving, not by 16 x 16 x K squares.** ``A_tj = sum_d
k_td k_jd exp(G_td - G_jd)`` (and ``B`` with ``q``). A pair ``t > j`` of
one chunk has a *level*, the highest bit ``h`` in which ``t`` and ``j``
differ: ``j`` is in the upper half and ``t`` in the lower half of one
block of ``2 h`` rows, and ``m``, the lower half's first row, lies
between them. Both operands are scaled to ``m``: the row by ``exp(G_t -
G_m)``, the column by ``exp(G_m - G_j)``, each exponent a difference of
running sums and at most 0 (``exp(-G)`` alone never appears), and a
level's scores are one plain matmul under the level's mask. At a level
every row is in one half or the other, so one ``[128, 128]`` array of
factors a level serves rows and columns both; ``G_m`` a row comes from
one sublane roll and select a level. Six levels (``h`` = 1 .. 32) and
the diagonal ``q_t . k_t`` are all of ``A`` and ``B``: the explicit
``[16, 16, K]`` form of the XLA path has no counterpart here. A level's
rows are the lower halves alone; from ``h`` = 8 up those are whole
sublane tiles, and only they are given to the MXU (``_lower``).

**The unit lower-triangular inverse by the same levels**: with ``X`` the
inverse of the blocks of ``h`` rows on the diagonal and ``R`` level
``h``'s part of ``Diag(beta) A``, ``X - X R X`` is the inverse of the
blocks of ``2 h`` rows (substitution by blocks: ``[[P, 0], [R, Q]]^-1 =
[[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``), two matmuls a level (none for
the first, whose blocks are single rows), exact, and no intermediate is
larger than an inverse of a diagonal block. A level's two stand in the
program behind the next level's scores, which do not wait for them.

Its backward is the inverse's own (``dL = -T^T dT T^T`` under the
diagonal, which with ``dT = dUv (beta v)^T + dW (beta k e^G)^T`` is
``-(T^T dUv) Uv^T - (T^T dW) W^T``), and the scores' backward needs no
cotangent of the reference row ``m``: its two contributions cancel.

**A decay a head** (``gdn_scan``: Gated DeltaNet's recurrence, which
``ops/kda.py::gdn_scan`` runs here). The same two kernels with ``rep``
set: ``g`` comes as ``beta`` does, ``[B, H, T / 128, 1, 128]``, one
float a row a head, and ``dg`` leaves so; a cell's block of ``q`` and
``k`` ``[B, T, Hk*128]`` holds the ``heads_a_cell / rep`` key heads that
its value heads read (value head ``j``, key head ``j // rep``), and the
backward writes a key head's ``dq, dk`` summed over its value heads, so
no copy of a key head and nothing ``K`` wide of the decay reaches HBM.
With one decay a row the scores need no levels (``_Cell.
_scalar_scores``): ``A = (K K^T) * D`` and ``B = (Q K^T) * D`` with
``D_tj = exp(G_t - G_j)``, one ``[256, 128] x [128, 128]`` product a
*key* head where the channels' decays take six a head, no rescaled copy
of q or k, and the backward through the scores is two products a value
head (``_scalar_tail``) where the channels' is twelve. The running sums
are kept ``[128, 128]`` with every lane alike, so the inverse by levels,
the state's carry and everything after the scores are the code above,
unchanged. Timed inside the Qwen3-Next cell's step at the same rows and
states (PERF.md section 6, PR 67): a layer's forward 10.0 ms and backward
17.1 where the channels' take 13.8 and 29.7.

Layout: ``q, k, g, v, o`` as ``[B, T, H*128]``, a head one 128-lane
block; ``beta`` as ``[B, H, T / 128, 1, 128]``, a cell's steps along
the lanes (the kernel turns them into a column under the identity
mask). The state is kept transposed, ``[V, K]``, so that what a chunk
keeps of it (``exp`` of the chunk's whole log-decay, a channel a lane)
scales lanes.

Precision: float32 operands, float32 accumulation, float32 ``exp`` and
``rsqrt`` (whatever dtype ``q``, ``k`` or ``v`` is stored in, nothing
below float32 is computed with),
every matmul at ``Precision.HIGHEST`` (Mosaic offers ``DEFAULT`` and
``HIGHEST``; the XLA path runs ``HIGH``).

Set-up: the two functions that hold the ``pallas_call``s are jitted, so
a model's layers, which call them at one shape, lower each kernel once
(PERF.md section 6, PR 28). The six levels and a cell's heads are
unrolled (four heads: ~8 s of Mosaic for the pair, compiled here for a
v5e; PERF.md section 6, PR 46).

What one v5e chip showed at 1 x 16,384 tokens, 32 heads (PERF.md
section 6, PR 46): a layer's forward 13.8 ms and backward 29.7 inside
the cell's step, where the XLA path took 50.7 and 115. The kernels are
bound by the MXU's passes: ``HIGHEST`` is six a product (one pass reads
11.9 ms where six read 20.2, forward, timed alone), and one head a cell
read 31.9 ms where two read 24.2 and four 23.9 before the lower rows
were packed (20.2 and 19.2 after).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ray_tpu.ops.remat import KDA_SCAN_OUT, KDA_SCAN_STATES

CHUNK = 64          # the recurrence's chunk; the configuration's
_ROWS = 2 * CHUNK   # rows of a grid cell: two chunks
_WIDTH = 128        # keys and values: one lane tile, and == _ROWS
_LEVELS = (1, 2, 4, 8, 16, 32)
_PACKED = 8         # levels from here up give the MXU only their lower rows
_F32 = jnp.float32
NORM_EPS = 1e-6                 # under the root of a row's unit length
_Q_SCALE = _WIDTH ** -0.5       # the unit queries' scale: head_dim^-1/2


def shapes_ok(kd: int, vd: int, chunk: int) -> bool:
    """Whether the kernels serve these widths: keys and values one
    128-lane tile a head, chunks of 64."""
    return kd == _WIDTH and vd == _WIDTH and chunk == CHUNK


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)


_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b.T
_TN = ((0,), (0,))      # a.T @ b


def _roll(x, shift: int):
    """Rows rolled down: ``out[t] = x[t - shift]``."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(x, shift % x.shape[0], 0)


def _rows_of(c: int) -> slice:
    return slice(c * CHUNK, (c + 1) * CHUNK)


def _last_row(x, c: int):
    """Chunk ``c``'s last row of ``x``, [1, width]."""
    return x[(c + 1) * CHUNK - 1:(c + 1) * CHUNK]


def _over_chunks(rows):
    """[1, width] a chunk -> [_ROWS, width], a chunk's row on each of
    its rows."""
    return jnp.concatenate(
        [jnp.broadcast_to(r, (CHUNK, r.shape[1])) for r in rows], axis=0)


def _lower(x, h: int):
    """The rows of ``x`` [_ROWS, .] in the lower half of their block of
    ``2 h`` rows, packed: [_ROWS / 2, .]. Whole sublane tiles for ``h``
    >= 8, so nothing moves; a smaller ``h`` keeps every row."""
    if h < _PACKED:
        return x
    return jnp.concatenate(
        [x[at + h:at + 2 * h] for at in range(0, _ROWS, 2 * h)], axis=0)


def _unpacked(x, h: int):
    """``_lower``'s inverse: the packed rows back in their places, the
    upper halves zero."""
    if h < _PACKED:
        return x
    zero = jnp.zeros((h, x.shape[1]), x.dtype)
    return jnp.concatenate(
        [z for at in range(0, _ROWS // 2, h) for z in (zero, x[at:at + h])],
        axis=0)


def _each(fn, *lists):
    """``fn`` a head: the kernels are written phase by phase over a
    cell's heads, so that one head's chain of dependent matmuls stands
    beside the others' in program order (Mosaic schedules close to the
    order it is given: PERF.md section 6, PR 31)."""
    return [fn(*z) for z in zip(*lists)]


class _Cell:
    """What both passes make of a cell's ``q, k, g, v, beta`` before the
    state is read. Every member but the masks is a list, a head an
    entry, of [128, 128] float32 arrays unless said: ``t`` runs down
    the rows, ``j`` (or a channel) along the lanes."""

    def __init__(self, q, k, g, v, beta_rows, *, keep_levels: bool,
                 scalar: bool = False):
        n = _ROWS
        t = lax.broadcasted_iota(jnp.int32, (n, n), 0)
        j = lax.broadcasted_iota(jnp.int32, (n, n), 1)
        self.differ = t ^ j                 # its highest bit: the level
        self.below = t > j
        self.eye = eye = t == j
        self.in_chunk = self.differ < CHUNK
        self.zero = zero = jnp.zeros((n, n), _F32)
        self.q, self.k, self.v = q, k, v
        self.beta = beta = _each(                               # [n, 1]
            lambda row: jnp.sum(jnp.where(eye, row, zero), axis=1,
                                keepdims=True), beta_rows)
        # the running sums inside each chunk
        self.ones = ones = jnp.where(self.in_chunk & (t >= j), 1.0, zero)
        if scalar:
            self._scalar_scores(q, k, g)
            self._after_scores()
            return
        G = _each(lambda g: _dot(ones, g, _NN), g)
        at_start = G            # G at the start of a row's block of h rows
        A = [zero] * len(q)
        B = _each(lambda q, k: jnp.where(
            eye, jnp.sum(q * k, axis=1, keepdims=True), zero), q, k)
        T, waiting = None, None
        self.levels = []

        for h in _LEVELS:
            lower = (t & h) != 0            # the row's half of its 2h block
            mask = self.level_mask(h)
            factor = _each(lambda G, s: jnp.exp(jnp.where(
                lower, G - s, _roll(s, -h) - G)), G, at_start)
            qs, ks = _each(jnp.multiply, q, factor), _each(
                jnp.multiply, k, factor)
            scores = _each(lambda qs, ks: _dot(jnp.concatenate(
                [_lower(qs, h), _lower(ks, h)], axis=0), ks, _NT), qs, ks)
            if waiting is not None:
                T = self._wider(T, *waiting)
            half = scores[0].shape[0] // 2
            waiting = _each(lambda s: jnp.where(
                mask, _unpacked(s[half:], h), zero), scores), h
            if keep_levels:     # the backward's, for beta's cotangent
                A = _each(jnp.add, A, waiting[0])
            # the levels' masks are disjoint
            B = _each(lambda B, s: jnp.where(
                mask, _unpacked(s[:half], h), B), B, scores)
            if keep_levels:
                self.levels.append((h, factor, qs, ks))
            if h != _LEVELS[-1]:
                at_start = _each(lambda s: jnp.where(lower, _roll(s, h), s),
                                 at_start)
        T = self._wider(T, *waiting)
        self.A, self.B, self.T, self.G = A, B, T, G
        self._after_scores()

    def _scalar_scores(self, q, k, g):
        """``A``, ``B`` and ``T`` where the decay is one number a row
        (``g``: a head's [1, rows] row, a cell's steps along the lanes):
        the decay leaves the products, ``A = (K K^T) * D`` and ``B = (Q
        K^T) * D`` with ``D_tj = exp(G_t - G_j)`` one matrix a head
        (every exponent a difference of running sums, at most 0 under
        the mask): one product a key head where the channels' decays
        take six levels of them, and no rescaled copy of q or k. ``q``
        and ``k`` are the key heads', ``len(g) / len(k)`` value heads
        to each. ``G`` is kept as the channels' is, every lane alike,
        so that all that follows reads it as it reads theirs."""
        n, zero, eye, ones = _ROWS, self.zero, self.eye, self.ones
        rep = len(g) // len(k)
        column = _each(lambda row: jnp.sum(
            jnp.where(eye, row, zero), axis=1, keepdims=True), g)
        G = _each(lambda c: _dot(ones, jnp.broadcast_to(c, (n, n)), _NN),
                  column)                       # G_t, on every lane
        across = _each(lambda row: _dot(jnp.broadcast_to(row, (n, n)), ones,
                                        _NT), g)    # G_j, on every row
        kept = self.in_chunk & (self.below | eye)
        self.D = D = _each(lambda G, Gj: jnp.exp(
            jnp.where(kept, G - Gj, -jnp.inf)), G, across)
        scores = _each(lambda q, k: _dot(
            jnp.concatenate([q, k], axis=0), k, _NT), q, k)     # [2 n, n]
        self.B = [scores[i // rep][:n] * D[i] for i in range(len(g))]
        self.A = A = [jnp.where(self.below, scores[i // rep][n:] * D[i],
                                zero) for i in range(len(g))]
        self.q = [q[i // rep] for i in range(len(g))]
        self.k = [k[i // rep] for i in range(len(g))]
        T = None
        for h in _LEVELS:
            mask = self.level_mask(h)
            T = self._wider(T, _each(lambda A: jnp.where(mask, A, zero), A),
                            h)
        self.T, self.G = T, G

    def _wider(self, T, a_level, h):
        """Blocks of h rows inverted -> blocks of 2h rows: ``T - T R T``
        with ``R`` level h's part of ``Diag(beta) A``, whose rows, like
        the product's, are the lower halves' alone. With the channels'
        decays a level's two matmuls stand in the program after the
        next level's scores, which do not wait for them."""
        eye = self.eye
        lower_left = _each(lambda b, a: _lower(b * a, h), self.beta, a_level)
        if T is None:       # blocks of one row: the identity
            return _each(lambda r: jnp.where(eye, 1.0, -r), lower_left)
        right = _each(lambda r, T: _unpacked(_dot(r, T, _NN), h),
                      lower_left, T)
        return _each(lambda T, right: T - _unpacked(
            _dot(_lower(T, h), right, _NN), h), T, right)

    def _after_scores(self):
        """What both forms of the scores share from here on."""
        G, q, k, v, beta, T = self.G, self.q, self.k, self.v, self.beta, \
            self.T
        ends = _each(lambda G: [_last_row(G, c) for c in range(2)], G)
        self.to_row = _each(jnp.exp, G)             # chunk's start -> row
        self.to_end = _each(                        # row -> chunk's end
            lambda G, ends: jnp.exp(_over_chunks(ends) - G), G, ends)
        self.keep = _each(                          # [1, K] a chunk
            lambda ends: [jnp.exp(e) for e in ends], ends)
        self.q_in = _each(jnp.multiply, q, self.to_row)
        self.k_in = _each(jnp.multiply, k, self.to_row)
        self.k_out = _each(jnp.multiply, k, self.to_end)
        uv_w = _each(lambda T, beta, v, k_in: _dot(T, jnp.concatenate(
            [beta * v, beta * k_in], axis=1), _NN), T, beta, v, self.k_in)
        self.Uv = [z[:, :_WIDTH] for z in uv_w]
        self.W = [z[:, _WIDTH:] for z in uv_w]

    def level_mask(self, h: int):
        return self.below & (self.differ >= h) & (self.differ < 2 * h)


def _read(ref, heads: int):
    """A cell's block of a ``[B, T, H*128]`` array, a head an entry."""
    return [ref[0, :, i * _WIDTH:(i + 1) * _WIDTH].astype(_F32)
            for i in range(heads)]


def _unit(x):
    """(``x``'s rows at unit length, the factor that brought them there
    [rows, 1]): ``x / sqrt(sum x^2 + 1e-6)``, float32, as the XLA path's
    ``qk_norm`` (``ops/kda.py::unit_rows``). A row of zeros stays zero."""
    r = lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + NORM_EPS)
    return x * r, r


def _unit_back(d, u, r):
    """``_unit``'s transpose: the cotangent of the rows as they came,
    from ``d``, the unit rows' ``u``, and the factor ``r``."""
    return r * (d - u * jnp.sum(d * u, axis=1, keepdims=True))


def _cell_of(q_ref, k_ref, g_ref, v_ref, beta_ref, *, heads, keep_levels,
             normalize, rep=None):
    """The cell, and (``normalize``) what the backward needs to carry
    ``dq, dk`` back to the rows as they came: (unit q, q's factor, k's
    factor), the unit k being the cell's own ``k``. ``rep``: None for a
    decay a channel; for a decay a head (``g_ref`` then holds rows like
    ``beta_ref``'s) the value heads to a key head, and ``q_ref``,
    ``k_ref`` hold the cell's ``heads / rep`` key heads."""
    scalar = rep is not None
    key_heads = heads // rep if scalar else heads
    q, k = _read(q_ref, key_heads), _read(k_ref, key_heads)
    g = ([g_ref[i] for i in range(heads)] if scalar
         else _read(g_ref, heads))
    v = _read(v_ref, heads)
    norms = None
    if normalize:
        q_unit, q_r = zip(*_each(_unit, q))
        k, k_r = zip(*_each(_unit, k))
        q = [u * _Q_SCALE for u in q_unit]
        norms = q_unit, q_r, k_r
    cell = _Cell(q, k, g, v, [beta_ref[i] for i in range(heads)],
                 keep_levels=keep_levels, scalar=scalar)
    return cell, norms


def _write(ref, arrays):
    for i, z in enumerate(arrays):
        ref[0, :, i * _WIDTH:(i + 1) * _WIDTH] = z.astype(ref.dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, g_ref, v_ref, beta_ref, o_ref, *rest,
                heads: int, keep_states: bool, normalize: bool, rep=None):
    enter_ref = rest[0] if keep_states else None
    state_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _first():
        state_ref[...] = jnp.zeros_like(state_ref)

    cell, _ = _cell_of(q_ref, k_ref, g_ref, v_ref, beta_ref, heads=heads,
                       keep_levels=False, normalize=normalize, rep=rep)
    state = [state_ref[i] for i in range(heads)]        # [V, K] a head
    read, U = [], []
    for c in range(2):
        r = _rows_of(c)
        if keep_states:
            for i in range(heads):
                enter_ref[c, i] = state[i]
        from_state = _each(lambda W, q_in, S: _dot(                # [2 C, V]
            jnp.concatenate([W[r], q_in[r]], axis=0), S, _NT),
            cell.W, cell.q_in, state)
        U.append(_each(lambda Uv, z: Uv[r] - z[:CHUNK], cell.Uv, from_state))
        read.append([z[CHUNK:] for z in from_state])
        own = _each(lambda U, k_out: _dot(U, k_out[r], _TN), U[c], cell.k_out)
        state = _each(lambda keep, S, own: keep[c] * S + own,
                      cell.keep, state, own)
    for i in range(heads):
        state_ref[i] = state[i]
    _write(o_ref, _each(
        lambda B, r0, r1, U0, U1: jnp.concatenate([r0, r1], axis=0) + _dot(
            B, jnp.concatenate([U0, U1], axis=0), _NN),
        cell.B, *read, *U))


def heads_a_cell(h: int) -> int:
    """Heads in a grid cell: their chains of dependent matmuls hide one
    another's latency, and a row's segment in HBM grows with them."""
    return next(n for n in (4, 2, 1) if h % n == 0)


def _specs(b_, h, steps, *, backward: bool):
    per = heads_a_cell(h)

    def step_of(s):
        return steps - 1 - s if backward else s

    rows = pl.BlockSpec((1, _ROWS, per * _WIDTH),
                        lambda b, hd, s: (b, step_of(s), hd))
    beta = pl.BlockSpec((None, per, None, 1, _ROWS),
                        lambda b, hd, s: (b, hd, step_of(s), 0, 0))
    enter = pl.BlockSpec((None, 2, per, _WIDTH, _WIDTH),
                         lambda b, hd, s: (b, step_of(s), hd, 0, 0))
    return (b_, h // per, steps), per, rows, beta, enter


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _state_scratch(heads: int):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM((heads, _WIDTH, _WIDTH), _F32)


@functools.partial(jax.jit, static_argnames=("keep_states", "normalize",
                                             "interpret"))
def _kda_fwd(q, k, g, v, beta, *, keep_states, normalize, interpret):
    """``o`` [B, T, H*V] float32 and, if asked, the state entering each
    chunk, transposed ([B, T / 64, H, V, K] float32). g [B, T, H*K]
    float32; q, k like it, or (``normalize``) the un-normalised rows in
    any dtype; v [B, T, H*V]; beta [B, H, T / 128, 1, 128] float32;
    ``T`` whole cells. Jitted so that a model's layers share one trace
    and one Mosaic lowering."""
    b_, t, hk = q.shape
    h, steps = hk // _WIDTH, t // _ROWS
    grid, per, rows, beta_s, enter = _specs(b_, h, steps, backward=False)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=per, keep_states=keep_states,
                          normalize=normalize),
        grid=grid,
        in_specs=[rows, rows, rows, rows, beta_s],
        out_specs=[rows] + [enter] * keep_states,
        out_shape=[jax.ShapeDtypeStruct(q.shape, _F32)] + [
            jax.ShapeDtypeStruct((b_, 2 * steps, h, _WIDTH, _WIDTH), _F32)
        ] * keep_states,
        scratch_shapes=[_state_scratch(per)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, g, v, beta)
    return tuple(out) if keep_states else (out[0], None)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, g_ref, v_ref, beta_ref, enter_ref, do_ref,
                dq_ref, dk_ref, dg_ref, dv_ref, dbeta_ref, dstate_ref, *,
                heads: int, normalize: bool, rep=None):
    """One cell, the cells walked last to first and the cell's second
    chunk before its first. ``dstate_ref`` carries the cotangent of the
    state *leaving* the chunk at hand, transposed like the state."""
    @pl.when(pl.program_id(2) == 0)
    def _first():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    cell, norms = _cell_of(q_ref, k_ref, g_ref, v_ref, beta_ref, heads=heads,
                           keep_levels=True, normalize=normalize, rep=rep)
    q, k, zero = cell.q, cell.k, cell.zero
    do = _read(do_ref, heads)
    entered = [[enter_ref[c, i] for c in range(2)]      # [V, K] a chunk
               for i in range(heads)]
    U = _each(lambda Uv, W, S: jnp.concatenate(
        [Uv[_rows_of(c)] - _dot(W[_rows_of(c)], S[c], _NT)
         for c in range(2)], axis=0), cell.Uv, cell.W, entered)
    kept = cell.in_chunk & (cell.below | cell.eye)
    dB = _each(lambda do, U: jnp.where(kept, _dot(do, U, _NT), zero), do, U)
    dU_own = _each(lambda B, do: _dot(B, do, _TN), cell.B, do)  # [rows, V]
    dstate = [dstate_ref[i] for i in range(heads)]
    dU, to_state, dk_out, d_end = ([None] * 2 for _ in range(4))
    for c in (1, 0):
        r = _rows_of(c)
        dU[c] = _each(lambda own, k_out, dS: own[r] + _dot(k_out[r], dS, _NT),
                      dU_own, cell.k_out, dstate)
        both = _each(lambda do, dU: jnp.concatenate([do[r], -dU], axis=0),
                     do, dU[c])                                 # [2 C, V]
        to_state[c] = _each(lambda both, S: _dot(both, S[c], _NN),
                            both, entered)                      # [2 C, K]
        dk_out[c] = _each(lambda U, dS: _dot(U[r], dS, _NN), U, dstate)
        d_end[c] = _each(
            lambda S, dS, keep, dk_out, k_out:
            jnp.sum(S[c] * dS, axis=0, keepdims=True) * keep[c]
            + jnp.sum(dk_out * k_out[r], axis=0, keepdims=True),
            entered, dstate, cell.keep, dk_out[c], cell.k_out)
        into = _each(lambda both, q_in, W: _dot(
            both, jnp.concatenate([q_in[r], W[r]], axis=0), _TN),
            both, cell.q_in, cell.W)
        dstate = _each(lambda keep, dS, into: keep[c] * dS + into,
                       cell.keep, dstate, into)
    for i in range(heads):
        dstate_ref[i] = dstate[i]

    def whole(per_chunk, rows=slice(None)):
        return _each(lambda a, b: jnp.concatenate([a[rows], b[rows]], axis=0),
                     *per_chunk)
    dU, dk_out = whole(dU), whole(dk_out)
    dq_in = whole(to_state, slice(0, CHUNK))
    dW = whole(to_state, slice(CHUNK, None))

    # through T: Uv, W = T [beta v, beta k_in]
    back = _each(lambda T, dU, dW: _dot(
        T, jnp.concatenate([dU, dW], axis=1), _TN), cell.T, dU, dW)
    dbv, dbk = [z[:, :_WIDTH] for z in back], [z[:, _WIDTH:] for z in back]
    under = cell.in_chunk & cell.below
    dL = _each(lambda back, Uv, W: -jnp.where(under, _dot(
        back, jnp.concatenate([Uv, W], axis=1), _NT), zero),
        back, cell.Uv, cell.W)
    dA = _each(jnp.multiply, cell.beta, dL)
    dbeta = _each(                                              # [rows, 1]
        lambda dL, A, dbv, v, dbk, k_in: jnp.sum(
            dL * A + dbv * v + dbk * k_in, axis=1, keepdims=True),
        dL, cell.A, dbv, cell.v, dbk, cell.k_in)
    _write(dv_ref, _each(jnp.multiply, cell.beta, dbv))
    dk_in = _each(jnp.multiply, cell.beta, dbk)
    if rep is not None:
        return _scalar_tail(
            cell, norms, rep, dB, dA, dq_in, dk_in, dk_out, d_end, dbeta,
            dq_ref, dk_ref, dg_ref, dbeta_ref)
    on_diag = _each(lambda dB: jnp.sum(jnp.where(cell.eye, dB, zero), axis=1,
                                       keepdims=True), dB)
    dq = _each(lambda dq_in, to_row, d, k: dq_in * to_row + d * k,
               dq_in, cell.to_row, on_diag, k)
    dk = _each(lambda dk_in, to_row, dk_out, to_end, d, q:
               dk_in * to_row + dk_out * to_end + d * q,
               dk_in, cell.to_row, dk_out, cell.to_end, on_diag, q)
    t = lax.broadcasted_iota(jnp.int32, (_ROWS, _ROWS), 0)
    dG = _each(
        lambda dq_in, q_in, dk_in, k_in, dk_out, k_out, e0, e1:
        dq_in * q_in + dk_in * k_in - dk_out * k_out
        + jnp.where(t == CHUNK - 1, e0, zero)
        + jnp.where(t == _ROWS - 1, e1, zero),
        dq_in, cell.q_in, dk_in, cell.k_in, dk_out, cell.k_out, *d_end)

    # through the scores, a level at a time
    for h, factor, qs, ks in cell.levels:
        mask = cell.level_mask(h)
        held = _each(lambda dB, dA: jnp.concatenate(        # [2 rows, .]
            [_lower(jnp.where(mask, z, zero), h) for z in (dB, dA)], axis=0),
            dB, dA)
        as_rows = _each(lambda held, ks: _dot(held, ks, _NN), held, ks)
        as_cols = _each(lambda held, qs, ks: _dot(held, jnp.concatenate(
            [_lower(qs, h), _lower(ks, h)], axis=0), _TN), held, qs, ks)
        half = as_rows[0].shape[0] // 2
        dqs = [_unpacked(z[:half], h) for z in as_rows]
        dks = [_unpacked(z[half:], h) for z in as_rows]
        dq = _each(lambda dq, dqs, f: dq + dqs * f, dq, dqs, factor)
        dk = _each(lambda dk, dks, cols, f: dk + (dks + cols) * f,
                   dk, dks, as_cols, factor)
        dG = _each(lambda dG, dqs, qs, dks, cols, ks:
                   dG + dqs * qs + (dks - cols) * ks,
                   dG, dqs, qs, dks, as_cols, ks)
    if normalize:       # back to the rows as they came, still in VMEM
        q_unit, q_r, k_r = norms
        dq = _each(lambda dq, u, r: _unit_back(dq * _Q_SCALE, u, r),
                   dq, q_unit, q_r)
        dk = _each(_unit_back, dk, k, k_r)
    _write(dq_ref, dq)
    _write(dk_ref, dk)
    _write(dg_ref, _each(lambda dG: _dot(cell.ones, dG, _TN), dG))
    for i in range(heads):
        dbeta_ref[i] = jnp.sum(jnp.where(cell.eye, dbeta[i], zero), axis=0,
                               keepdims=True)


def _scalar_tail(cell, norms, rep, dB, dA, dq_in, dk_in, dk_out, d_end,
                 dbeta, dq_ref, dk_ref, dg_ref, dbeta_ref):
    """The backward kernel from the scores on, for a decay a head: with
    ``A = (K K^T) * D`` and ``B = (Q K^T) * D`` the scores' cotangents
    go back through one pair of products a value head, a key head's
    ``dq, dk`` are its value heads' summed, and the running sum's
    cotangent is one number a row: the channels' terms summed over the
    lanes, plus ``sum_j P_tj - sum_j P_jt`` with ``P = dB * B + dA * A``
    (a score's exponent is ``G_t - G_j``). ``dg`` leaves as ``g`` came,
    a head's steps along the lanes."""
    n, zero, eye, ones = _ROWS, cell.zero, cell.eye, cell.ones
    q, k = cell.q, cell.k
    through = _each(lambda dB, dA, D: jnp.concatenate(
        [dB * D, dA * D], axis=0), dB, dA, cell.D)              # [2 n, n]
    as_rows = _each(lambda th, k: _dot(th, k, _NN), through, k)
    as_cols = _each(lambda th, q, k: _dot(
        th, jnp.concatenate([q, k], axis=0), _TN), through, q, k)
    dq = _each(lambda dq_in, to_row, rows: dq_in * to_row + rows[:n],
               dq_in, cell.to_row, as_rows)
    dk = _each(lambda dk_in, to_row, dk_out, to_end, rows, cols:
               dk_in * to_row + dk_out * to_end + rows[n:] + cols,
               dk_in, cell.to_row, dk_out, cell.to_end, as_rows, as_cols)
    # a key head's: its value heads' summed
    dq = [sum(dq[i:i + rep][1:], dq[i]) for i in range(0, len(dq), rep)]
    dk = [sum(dk[i:i + rep][1:], dk[i]) for i in range(0, len(dk), rep)]
    if norms is not None:
        q_unit, q_r, k_r = norms
        dq = _each(lambda dq, u, r: _unit_back(dq * _Q_SCALE, u, r),
                   dq, q_unit, q_r)
        dk = _each(_unit_back, dk, k[::rep], k_r)
    _write(dq_ref, dq)
    _write(dk_ref, dk)
    t = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    ones_t = jnp.where(cell.in_chunk & (j >= t), 1.0, zero)     # ones^T
    for i in range(len(k)):
        wide = (dq_in[i] * cell.q_in[i] + dk_in[i] * cell.k_in[i]
                - dk_out[i] * cell.k_out[i]
                + jnp.where(t == CHUNK - 1, d_end[0][i], zero)
                + jnp.where(t == n - 1, d_end[1][i], zero))
        P = dB[i] * cell.B[i] + dA[i] * cell.A[i]
        down = jnp.sum(wide + P, axis=1, keepdims=True)         # [n, 1], t
        across = jnp.sum(P, axis=0, keepdims=True)              # [1, n], j
        # dg_s = sum_{t >= s, t in s's chunk} dG_t, a step a lane
        back = jnp.sum(ones_t * across, axis=1, keepdims=True)  # [n, 1], s
        dg_ref[i] = (jnp.sum(ones * down, axis=0, keepdims=True)
                     - jnp.sum(jnp.where(eye, back, zero), axis=0,
                               keepdims=True))
        dbeta_ref[i] = jnp.sum(jnp.where(eye, dbeta[i], zero), axis=0,
                               keepdims=True)


@functools.partial(jax.jit, static_argnames=("normalize", "interpret"))
def _kda_bwd(q, k, g, v, beta, entering, do, *, normalize, interpret):
    """(dq like q, dk like k, dg [B, T, H*K] float32, dv like v, dbeta
    like beta); jitted for the reason ``_kda_fwd`` is."""
    b_, t, hk = q.shape
    h, steps = hk // _WIDTH, t // _ROWS
    grid, per, rows, beta_s, enter = _specs(b_, h, steps, backward=True)
    dq, dk, dg, dv = (jax.ShapeDtypeStruct(z.shape, z.dtype)
                      for z in (q, k, g, v))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=per, normalize=normalize),
        grid=grid,
        in_specs=[rows, rows, rows, rows, beta_s, enter, rows],
        out_specs=[rows, rows, rows, rows, beta_s],
        out_shape=[dq, dk, dg, dv, jax.ShapeDtypeStruct(beta.shape, _F32)],
        scratch_shapes=[_state_scratch(per)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, g, v, beta, entering, do)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_core(q, k, g, v, beta, normalize: bool, interpret: bool):
    return _kda_fwd(q, k, g, v, beta, keep_states=False, normalize=normalize,
                    interpret=interpret)[0]


def _kda_core_fwd(q, k, g, v, beta, normalize, interpret):
    o, entering = _kda_fwd(q, k, g, v, beta, keep_states=True,
                           normalize=normalize, interpret=interpret)
    # both named before they part into primal and residuals (the trap
    # ``ops/remat.py::name_core_results`` records): a name on ``o``
    # alone would leave ``entering`` to be made again, the kernel with it
    o, entering = checkpoint_name(o, KDA_SCAN_OUT), checkpoint_name(
        entering, KDA_SCAN_STATES)
    return o, (q, k, g, v, beta, entering)


def _kda_core_bwd(normalize, interpret, res, do):
    return tuple(_kda_bwd(*res, do.astype(_F32), normalize=normalize,
                          interpret=interpret))


_kda_core.defvjp(_kda_core_fwd, _kda_core_bwd)


def kda_scan(q, k, v, g, beta, *, normalize_qk: bool = False,
             interpret: bool = False):
    """``ops/kda.py::kda_scan`` on the kernels: the same arguments (q, k,
    g [b, T, H, 128] float32; v [b, T, H, 128]; beta [b, T, H]), the
    same result ``o`` [b, T, H, 128] float32, differentiable in all
    five. With ``normalize_qk`` (``ops/kda.py`` sets it) ``q`` and ``k``
    are the un-normalised rows in whatever dtype they have: the kernels
    read them so, make ``unit(q) * 128^-1/2`` and ``unit(k)`` in VMEM in
    float32, and return ``dq, dk`` of the rows as they came, in their
    dtype. ``T`` need not be whole cells: the tail is padded with rows
    that neither decay nor write the state (a row of zeros stays zero
    under the norm)."""
    b_, t, h, kd = q.shape
    if not shapes_ok(kd, v.shape[-1], CHUNK):
        raise ValueError(f"the kernels do not tile keys {kd}, values "
                         f"{v.shape[-1]}")
    pad = (-t) % _ROWS

    def rows(z):
        z = z.reshape(b_, t, -1)
        return jnp.pad(z, ((0, 0), (0, pad), (0, 0))) if pad else z

    steps = jnp.swapaxes(rows(beta.astype(_F32)), 1, 2).reshape(
        b_, h, (t + pad) // _ROWS, 1, _ROWS)
    if not normalize_qk:    # unit rows are float32 rows
        q, k = q.astype(_F32), k.astype(_F32)
    o = _kda_core(rows(q), rows(k), rows(g.astype(_F32)), rows(v), steps,
                  normalize_qk, interpret)
    return o[:, :t].reshape(b_, t, h, -1)


# ---------------------------------------------------------------------------
# a decay a head (Gated DeltaNet): the same kernels, the scalar scores
# ---------------------------------------------------------------------------

def heads_ok(heads: int, key_heads: int) -> bool:
    """Whether a grid cell's value heads are whole key heads' groups."""
    return (key_heads > 0 and heads % key_heads == 0
            and heads_a_cell(heads) % (heads // key_heads) == 0)


def _gdn_specs(b_, h, rep, steps, *, backward: bool):
    """``_specs`` with the key heads' blocks: a cell's ``per / rep`` key
    heads of ``q`` and ``k`` [B, T, (H / rep) * 128], walked as the
    value heads' rows are."""
    grid, per, rows, beta, enter = _specs(b_, h, steps, backward=backward)
    keys = pl.BlockSpec((1, _ROWS, per // rep * _WIDTH), rows.index_map)
    return grid, per, rows, keys, beta, enter


@functools.partial(jax.jit, static_argnames=("keep_states", "normalize",
                                             "interpret"))
def _gdn_fwd(q, k, g, v, beta, *, keep_states, normalize, interpret):
    """``_kda_fwd`` for a decay a head: q, k [B, T, Hk*128]; v [B, T,
    H*128]; g like beta, [B, H, T / 128, 1, 128] float32."""
    b_, t, hv = v.shape
    h, steps = hv // _WIDTH, t // _ROWS
    rep = hv // q.shape[-1]
    grid, per, rows, keys, beta_s, enter = _gdn_specs(b_, h, rep, steps,
                                                      backward=False)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=per, keep_states=keep_states,
                          normalize=normalize, rep=rep),
        grid=grid,
        in_specs=[keys, keys, beta_s, rows, beta_s],
        out_specs=[rows] + [enter] * keep_states,
        out_shape=[jax.ShapeDtypeStruct(v.shape, _F32)] + [
            jax.ShapeDtypeStruct((b_, 2 * steps, h, _WIDTH, _WIDTH), _F32)
        ] * keep_states,
        scratch_shapes=[_state_scratch(per)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, g, v, beta)
    return tuple(out) if keep_states else (out[0], None)


@functools.partial(jax.jit, static_argnames=("normalize", "interpret"))
def _gdn_bwd(q, k, g, v, beta, entering, do, *, normalize, interpret):
    """(dq like q, dk like k, dg like g, dv like v, dbeta like beta):
    ``dg`` one float a row a head."""
    b_, t, hv = v.shape
    h, steps = hv // _WIDTH, t // _ROWS
    rep = hv // q.shape[-1]
    grid, per, rows, keys, beta_s, enter = _gdn_specs(b_, h, rep, steps,
                                                      backward=True)
    dq, dk, dv = (jax.ShapeDtypeStruct(z.shape, z.dtype) for z in (q, k, v))
    steps_f32 = jax.ShapeDtypeStruct(beta.shape, _F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=per, normalize=normalize,
                          rep=rep),
        grid=grid,
        in_specs=[keys, keys, beta_s, rows, beta_s, enter, rows],
        out_specs=[keys, keys, beta_s, rows, beta_s],
        out_shape=[dq, dk, steps_f32, dv, steps_f32],
        scratch_shapes=[_state_scratch(per)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, g, v, beta, entering, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdn_core(q, k, g, v, beta, normalize: bool, interpret: bool):
    return _gdn_fwd(q, k, g, v, beta, keep_states=False, normalize=normalize,
                    interpret=interpret)[0]


def _gdn_core_fwd(q, k, g, v, beta, normalize, interpret):
    o, entering = _gdn_fwd(q, k, g, v, beta, keep_states=True,
                           normalize=normalize, interpret=interpret)
    # named as ``_kda_core_fwd`` names them, and for its reason
    o, entering = checkpoint_name(o, KDA_SCAN_OUT), checkpoint_name(
        entering, KDA_SCAN_STATES)
    return o, (q, k, g, v, beta, entering)


def _gdn_core_bwd(normalize, interpret, res, do):
    return tuple(_gdn_bwd(*res, do.astype(_F32), normalize=normalize,
                          interpret=interpret))


_gdn_core.defvjp(_gdn_core_fwd, _gdn_core_bwd)


def gdn_scan(q, k, v, g, beta, *, normalize_qk: bool = False,
             interpret: bool = False):
    """``ops/kda.py::gdn_scan`` on the kernels: q, k [b, T, Hk, 128]; v
    [b, T, H, 128]; g, beta [b, T, H] (the decay a head); ``o`` [b, T,
    H, 128] float32, differentiable in all five. ``normalize_qk`` and
    the tail as ``kda_scan``'s. The key heads are never repeated in
    HBM: a cell's block of ``q`` and ``k`` holds the ``heads_a_cell / (H
    / Hk)`` key heads its value heads read, and writes their ``dq, dk``
    summed over those value heads. ``g`` goes in, and ``dg`` comes out,
    in ``beta``'s layout, one float a row a head."""
    b_, t, h, vd = v.shape
    if not (shapes_ok(q.shape[-1], vd, CHUNK) and heads_ok(h, q.shape[2])):
        raise ValueError(f"the kernels do not tile keys {q.shape}, values "
                         f"{v.shape}")
    pad = (-t) % _ROWS

    def rows(z):
        z = z.reshape(b_, t, -1)
        return jnp.pad(z, ((0, 0), (0, pad), (0, 0))) if pad else z

    def steps(z):       # [b, T, H] -> [b, H, T / 128, 1, 128]
        return jnp.swapaxes(rows(z.astype(_F32)), 1, 2).reshape(
            b_, h, (t + pad) // _ROWS, 1, _ROWS)

    if not normalize_qk:    # unit rows are float32 rows
        q, k = q.astype(_F32), k.astype(_F32)
    o = _gdn_core(rows(q), rows(k), steps(g), rows(v), steps(beta),
                  normalize_qk, interpret)
    return o[:, :t].reshape(b_, t, h, -1)

"""The routed layer's row moves without a scatter: ``take_rows`` (the
sorted routes' tokens' rows, ``ops/moe.py``'s ``dispatch``) and
``sum_rows`` (each token's weighted sum of its routes' rows, its
``combine``), each the other's transpose under one ``custom_vjp``.

What they replace. Up to PR 41 a slab of the held path gathered its
rows and scatter-added the experts' answers into a float32 ``[t, d]``;
the backward ran the transposes, a float32 gather and a scatter-add.
On one v5e at SmallThinker's shape (49,152 rows of 2,560, 24,477 live;
PERF.md section 6, PR 43) XLA's gather of the whole slab takes 1.23 ms,
25 ns a row, dead rows and all; the float32 scatter-add 8.1 ms, 165 ns
a row. The scatter-adds were the bill, not the dead rows.

``take_rows`` is XLA's gather (rows past the live ones masked to
**zero**). ``sum_rows`` on a TPU is a gather and a matrix product:

1. the slab's live rows in token order (one ``lax.sort`` of the slab's
   route numbers, then XLA's gather of the rows by it);
2. megablox ``tgmm`` over them, a group a tile of ``TILE`` tokens:
   ``out[g] = onehot[rows of g]^T @ rows of g``, where row ``i`` of
   ``onehot`` holds the route's weight in the column of its token
   inside the tile. The MXU adds each token's routes in float32 and
   the tile is written once, in ``src``'s dtype; the kernel's grid
   runs over the tiles that hold live rows (``num_active_tiles``), so a
   dead row is never read, whatever it holds, and a tile of tokens
   with no route here is written as zeros.

A weight is rounded to ``src``'s dtype first, as the product's other
factor is; a product of two bfloat16 values is exact in float32, so a
top-1 token's row is the old path's bit for bit and a top-k token's
sum is the float32 sum of its exact products.

Tried and left out (PERF.md section 6, PR 43): a Pallas kernel that
copies the landed rows alone, one DMA a row, HBM to HBM. The copies
ran at 27 ns a landed row, but Mosaic refuses a DMA that slices one
row of a tiled dimension, so every operand had to be repacked as
``[rows, 1, words]`` by XLA passes that cost more than the dead rows.

Off the TPU, where a program spans devices outside a ``shard_map`` (a
``pallas_call`` has no SPMD rule), where ``TILE`` does not divide the
tokens, and in the loops over further slabs (``ops/moe.py::
_overflow``), ``rows`` is ``"xla"``: ``sum_rows`` gathers each token's
``k`` routes by ``pos``, one ``j`` a turn, and adds them under a mask,
no scatter either. ``"interpret"`` runs ``tgmm`` in the interpreter for the CPU
tests.
"""

from __future__ import annotations

import functools
import importlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32
# Tokens a group of the summing product covers: the one-hot's width.
TILE = 128
# Rows of the sorted slab a grid step of it multiplies.
_ROWS = 256


def rows_path(tokens: int, rows: int) -> str:
    """Which ``sum_rows`` a routed layer over ``tokens`` tokens in
    slabs of ``rows`` compiles on this backend: ``tgmm`` (the gather
    and the one-hot product) on a TPU where the tiles divide both,
    ``xla`` (gathers by ``pos``) elsewhere; chosen from the backend, as
    ``ops/moe.py::grouped_matmul_path``."""
    if (jax.default_backend() == "tpu" and tokens % TILE == 0
            and rows % min(_ROWS, rows) == 0):
        return "tgmm"
    return "xla"


def width_tile(d: int, most: int = 1024) -> int:
    """The tile of 128-lane multiples between ``most / 2`` and ``most``
    that pads a dimension of ``d`` least (2,688 = 3 x 896; 1,856 into
    3 x 640 rather than 2 x 1,024), the larger of equals; a dimension
    under it is taken whole by the caller (``min(tile, d)``)."""
    fits = range(most // 2, most + 1, 128)
    return min(fits, key=lambda t: (-(-d // t) * t, -t))


# ---------------------------------------------------------------------------
# take_rows / sum_rows
# ---------------------------------------------------------------------------

class Slab(NamedTuple):
    """Where a slab's rows come from and go to. ``route`` [R]: the
    sorted routes of the slab, route ``i`` being route ``route[i] % k``
    of token ``route[i] // k``; ``pos`` [t, k]: each route's place in
    the whole sorted order (``route``'s inverse); ``lo``: the place of
    the slab's first row; ``n_live``: how many places, from 0 on, hold
    a route that landed on a held expert."""
    route: jax.Array
    pos: jax.Array
    lo: jax.Array
    n_live: jax.Array

    def sorted_index(self):
        """The token of each row of the slab, -1 past the live ones."""
        rows, k = self.route.shape[0], self.pos.shape[-1]
        live = self.lo + jnp.arange(rows, dtype=jnp.int32) < self.n_live
        return jnp.where(live, self.route // k, -1)

    def token_index(self):
        """[t * k]: the slab's row of each route, -1 for a route that
        is not in the slab or did not land."""
        rows = self.route.shape[0]
        at = self.pos.reshape(-1) - self.lo
        live = (at >= 0) & (at < rows) & (self.pos.reshape(-1) < self.n_live)
        return jnp.where(live, at, -1)


@jax.jit
def _take(src, slab: Slab):
    """``take_rows``; jitted, as ``_sum`` is, so that a step's layers
    and passes trace each once (PERF.md section 6, PR 28)."""
    idx = slab.sorted_index()
    k = slab.pos.shape[-1]
    return jnp.where((idx >= 0)[:, None], src[slab.route // k],
                     jnp.zeros((), src.dtype))


@functools.partial(jax.jit, static_argnames=("rows", "dtype"))
def _sum(src, w, slab: Slab, rows: str, dtype):
    """``sum_rows`` with ``w`` None for unit weights and the result in
    ``dtype``."""
    if rows == "xla":
        return _sum_by_pos(src, w, slab, dtype)
    # the package exports a function under the module's name
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    t, k = slab.pos.shape
    n, d = src.shape
    # the live rows' route numbers ascend with (token, j): token order
    key, at = lax.sort(
        (jnp.where(slab.sorted_index() >= 0, slab.route,
                   jnp.iinfo(jnp.int32).max), jnp.arange(n, dtype=jnp.int32)),
        num_keys=1)
    weight = (jnp.ones((), src.dtype) if w is None else
              w.astype(src.dtype).reshape(-1)[jnp.minimum(key, t * k - 1)])
    onehot = jnp.where(
        (key // k % TILE)[:, None] == jnp.arange(TILE, dtype=jnp.int32),
        jnp.reshape(weight, (-1, 1)), jnp.zeros((), src.dtype))
    sizes = (slab.token_index() >= 0).reshape(t // TILE, -1).sum(
        axis=-1, dtype=jnp.int32)
    out = megablox.tgmm(
        onehot.T, src[at], sizes, jnp.dtype(dtype),
        (min(_ROWS, n), TILE, min(width_tile(d), d)),
        interpret=rows == "interpret")
    return out.reshape(t, d)


def _sum_by_pos(src, w, slab: Slab, dtype):
    """The plain form: each token's ``j``-th route gathered by ``pos``
    and added under its mask, one ``j`` a turn so that one ``[t, d]``
    of gathered rows exists at a time, not ``k``."""
    t, k = slab.pos.shape
    idx = slab.token_index().reshape(t, k)
    wf = None if w is None else w.astype(src.dtype).astype(_F32)

    def add(j, acc):
        at = lax.dynamic_index_in_dim(idx, j, axis=1, keepdims=False)
        got = jnp.where((at >= 0)[:, None], src[jnp.maximum(at, 0)],
                        jnp.zeros((), src.dtype)).astype(_F32)
        if wf is not None:
            got = got * lax.dynamic_index_in_dim(wf, j, axis=1)
        return acc + got

    return lax.fori_loop(0, k, add, jnp.zeros((t, src.shape[-1]), _F32)
                         ).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def take_rows(src, slab: Slab, rows: str = "xla"):
    """Row ``i`` of the result is ``src[token of the slab's row i]``
    where that row is live, zero past the live ones. src [t, d] ->
    [R, d]. The backward is ``sum_rows`` of the cotangent with unit
    weights, on the path ``rows`` names."""
    return _take(src, slab)


def _take_fwd(src, slab, rows):
    return _take(src, slab), slab


def _take_bwd(rows, slab, g):
    # under the call's own scope (``jvp(dispatch)`` in the op's name)
    return _sum(g, None, slab, rows, g.dtype), None


take_rows.defvjp(_take_fwd, _take_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def sum_rows(src, w, slab: Slab, rows: str = "xla", dtype=None):
    """Row ``t`` of the result is the float32 sum over token ``t``'s
    routes ``j`` that are live rows of the slab of ``w[t, j]`` (rounded
    to ``src``'s dtype first, as the product's other factor is) times
    that row of ``src``, cast once to ``dtype`` (default: ``src``'s).
    src [R, d], w [t, k] float32 -> [t, d]. Backward: ``take_rows`` of
    the cotangent, times the weights for ``src`` and dotted with
    ``src`` for ``w``."""
    return _sum(src, w, slab, rows, dtype or src.dtype)


def _sum_fwd(src, w, slab, rows, dtype):
    return _sum(src, w, slab, rows, dtype or src.dtype), (src, w, slab)


@jax.jit
def _sum_pull(src, w, slab: Slab, dy):
    g = _take(dy.astype(src.dtype), slab)                    # [R, d]
    w_sorted = w.astype(src.dtype).reshape(-1)[slab.route]
    # a dead row of ``g`` is zero, of ``src`` anything at all
    dots = jnp.where(slab.sorted_index() >= 0,
                     jnp.sum(g.astype(_F32) * src.astype(_F32), axis=-1), 0.0)
    idx = slab.token_index()
    dw = jnp.where(idx >= 0, dots[jnp.maximum(idx, 0)], 0.0)
    return g * w_sorted[:, None], dw.reshape(w.shape).astype(w.dtype)


def _sum_bwd(rows, dtype, res, dy):
    return (*_sum_pull(*res, dy), None)


sum_rows.defvjp(_sum_fwd, _sum_bwd)

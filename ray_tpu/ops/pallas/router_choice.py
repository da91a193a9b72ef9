"""A router's choice as one kernel pair: everything between the float32
product ``x W`` ``[T, E]`` and what the routed layer reads of it
(``ops/moe.py::_route`` / ``_route_sigmoid``): the scores, the ``top_k``
largest with the lowest index first among equals, the chosen scores,
the routes each expert received, and the softmax router's two sums.

What it replaces. In XLA the choice is ``lax.top_k``, a gather of the
``[T, k]`` chosen scores from ``[T, E]``, that gather's scatter-add in
the backward pass and the counts' scatter-add of ``T k`` ones: ~6 ms a
layer at 16,384 tokens and 512 experts on one v5e, where the float32
product at the highest precision is ~3 (PERF.md section 6, PRs 66-68).
XLA's gather costs 25 ns a row and its float32 scatter-add 331-347 ns a
row whatever a row holds, and a router's ``T k`` chosen scalars are
moved as that many rows.

The layout. The kernels read the product **transposed**, ``[E, T]``, a
tile of tokens along the lanes: a maximum over the experts is then an
elementwise maximum across vregs and one fold of eight sublanes, no
cross-lane reduction; every result is lane-dense (``[k, T]`` rows, the
``lse`` ``[1, T]``); 64 experts tile like 512. The product's transpose
is XLA's, inside the jitted pair (it makes the product that way round
where it can); the cotangent's is the backward kernel's own, whole
128 x 128 tiles in VMEM, so that the router's two backward products
read ``[T, E]`` as they did.

Forward, a tile of tokens a grid step, everything float32 in VMEM:

- the scores: ``exp(logits - logsumexp(logits))`` for ``"softmax"``,
  ``sigmoid(logits)`` for ``"sigmoid"``;
- ``top_k`` rounds over the keys (the softmax's probabilities; the
  sigmoid's scores **plus** ``select_bias``): the maximum over the
  experts, the lowest index that attains it (``lax.top_k``'s rule),
  that entry masked out with ``-inf`` (so the keys must be finite: a
  ``select_bias`` of ``-inf`` is not a way to close an expert here);
- the chosen weights: the probabilities, or the sigmoid's scores
  **without** the bias;
- the routes each expert received, as the sum over the tile's tokens of
  the entries masked out, folded to 128 lanes and added up over the grid
  in the resident ``[E, 128]`` result; the last fold is XLA's;
- for the softmax: the probabilities' sum over tokens the same way, and
  ``lse`` ``[T]`` (the z-loss's ``sum(lse^2)`` is the caller's, a pass
  over ``[T]``).

Backward, one pass over the same tiles, the scores made again in VMEM
from the kept product (and ``lse``), never written:

    d scores[e, t] = sum_j d weights[j, t] (experts[j, t] == e) + d prob_sum[e]
    softmax:  d logits = p (d scores - sum_e(d scores p) + d lse)
    sigmoid:  d logits = d scores s (1 - s)

``d lse`` carries the z-loss's ``2 lse d z_sum``. No gather, no
scatter-add, and no ``[T, E]`` array but the product and its cotangent
are in HBM in either pass; ``select_bias`` takes no gradient.

The forward rule names ``experts``, ``weights``, ``counts`` and ``lse``
for a recomputed block's policy (``ROUTER_*``; ``ops/moe.py::
ROUTER_KEEPS``): a block that keeps them and the product runs neither
kernel nor ``top_k`` a second time.

``router_path`` says where the pair runs: on a TPU where the shapes
tile. A ``pallas_call`` has no SPMD rule, so a program that spans
devices calls it a shard at a time inside ``ops/moe.py``'s
``shard_map``, or stays on XLA's lines.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

# the names of what the choice makes, for a recomputed block's policy
from ray_tpu.ops.remat import (
    ROUTER_COUNTS, ROUTER_EXPERTS, ROUTER_LSE, ROUTER_WEIGHTS)

_F32 = jnp.float32
_LANES = 128
# float32 entries of the product a grid step holds: 512 KB a block, a
# few MB of VMEM with the rounds' temporaries.
_BLOCK = 128 * 1024
_VMEM_LIMIT = 64 << 20


def shapes_ok(tokens: int, experts: int, top_k: int) -> bool:
    """Whether the kernels tile a ``[tokens, experts]`` product: whole
    128-lane tiles of tokens, whole sublanes of experts."""
    return (tokens > 0 and tokens % _LANES == 0 and experts % 8 == 0
            and 0 < top_k <= experts)


def router_path(tokens: int, experts: int, top_k: int) -> str:
    """Which choice a router over ``tokens`` tokens (a chip's) compiles
    on this backend: ``pallas`` (this file's pair) on a TPU where the
    shapes tile, ``xla`` (``top_k``, the gather, the scatter-adds)
    elsewhere; chosen from the backend, as ``route_rows.rows_path``."""
    if jax.default_backend() == "tpu" and shapes_ok(tokens, experts, top_k):
        return "pallas"
    return "xla"


def _tile(tokens: int, experts: int) -> int:
    """Tokens a grid step: the largest power-of-two multiple of 128 that
    divides ``tokens`` and keeps a block under ``_BLOCK`` entries."""
    tile = _LANES
    while tile * 2 * experts <= _BLOCK and tokens % (tile * 2) == 0:
        tile *= 2
    return tile


def _fold(a):
    """[E, tile] -> [E, 128]: the tile's 128-lane pieces added up."""
    return sum(a[:, c:c + _LANES] for c in range(0, a.shape[1], _LANES))


def _scores(activation, logits, lse=None):
    if activation == "sigmoid":
        return jax.nn.sigmoid(logits), None
    if lse is None:
        top = jnp.max(logits, axis=0, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=0,
                              keepdims=True)) + top
    return jnp.exp(logits - lse), lse


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, top_k, activation):
    if activation == "sigmoid":
        logits_ref, bias_ref, w_ref, e_ref, counts_ref = refs
    else:
        logits_ref, w_ref, e_ref, counts_ref, probs_ref, lse_ref = refs
    e, tile = logits_ref.shape
    scores, lse = _scores(activation, logits_ref[...])
    keys = scores + bias_ref[...] if activation == "sigmoid" else scores
    # indices as floats: exact, and a float minimum folds like the maximum
    iota = lax.broadcasted_iota(jnp.int32, (e, tile), 0).astype(_F32)
    for j in range(top_k):
        best = jnp.max(keys, axis=0, keepdims=True)
        at = jnp.minimum(
            jnp.min(jnp.where(keys == best, iota, float(e)), axis=0,
                    keepdims=True), float(e - 1))
        hit = iota == at
        w_ref[j:j + 1, :] = best if activation == "softmax" else jnp.sum(
            jnp.where(hit, scores, 0.0), axis=0, keepdims=True)
        e_ref[j:j + 1, :] = at.astype(jnp.int32)
        keys = jnp.where(hit, -jnp.inf, keys)

    @pl.when(pl.program_id(0) == 0)
    def _first():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        if activation == "softmax":
            probs_ref[...] = jnp.zeros_like(probs_ref)

    counts_ref[...] += _fold((keys == -jnp.inf).astype(jnp.int32))
    if activation == "softmax":
        probs_ref[...] += _fold(scores)
        lse_ref[...] = lse


def _compiler_params(semantics: str):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
                                vmem_limit_bytes=_VMEM_LIMIT)


def _specs(e: int, top_k: int, tile: int):
    """(a tile of the product, of a ``[k, T]`` array, of a ``[1, T]``
    one, a resident ``[E, n]`` array)."""
    return (pl.BlockSpec((e, tile), lambda i: (0, i)),
            pl.BlockSpec((top_k, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            lambda n: pl.BlockSpec((e, n), lambda i: (0, 0)))


_STATIC = ("top_k", "activation", "tile", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _choice_fwd(logits, bias, *, top_k, activation, tile, interpret):
    """logits [T, E] float32 (and ``bias`` [E] for the sigmoid) ->
    (weights [T, k], experts [T, k] int32, counts [E] int32, the
    probabilities' sum [E], lse [T]; zeros for the last two of the
    sigmoid). Jitted so that a model's layers share one trace and one
    Mosaic lowering."""
    t, e = logits.shape
    block, rows, row, resident = _specs(e, top_k, tile)
    shape = jax.ShapeDtypeStruct
    operands, in_specs = [logits.T], [block]
    out_specs = [rows, rows, resident(_LANES)]
    out_shape = [shape((top_k, t), _F32), shape((top_k, t), jnp.int32),
                 shape((e, _LANES), jnp.int32)]
    if activation == "sigmoid":
        operands.append(bias.astype(_F32)[:, None])
        in_specs.append(resident(1))
    else:
        out_specs += [resident(_LANES), row]
        out_shape += [shape((e, _LANES), _F32), shape((1, t), _F32)]
    w, experts, counts, *sums = pl.pallas_call(
        functools.partial(_fwd_kernel, top_k=top_k, activation=activation),
        grid=(t // tile,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        # the counts and the sum are added up over the grid
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret)(*operands)
    if sums:
        prob_sum, lse = sums[0].sum(axis=1), sums[1][0]
    else:
        prob_sum, lse = jnp.zeros((e,), _F32), jnp.zeros((t,), _F32)
    return w.T, experts.T, counts.sum(axis=1), prob_sum, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(*refs, top_k, activation):
    if activation == "sigmoid":
        logits_ref, e_ref, dw_ref, out_ref = refs
    else:
        (logits_ref, e_ref, dw_ref, lse_ref, dprobs_ref, dlse_ref,
         out_ref) = refs
    e, tile = logits_ref.shape
    iota = lax.broadcasted_iota(jnp.int32, (e, tile), 0)
    g = jnp.zeros((e, tile), _F32)
    for j in range(top_k):      # a token's experts are all different
        g = jnp.where(iota == e_ref[j:j + 1, :], dw_ref[j:j + 1, :], g)
    if activation == "sigmoid":
        s, _ = _scores(activation, logits_ref[...])
        out = g * s * (1.0 - s)
    else:
        p, _ = _scores(activation, logits_ref[...], lse_ref[...])
        g = g + dprobs_ref[...]
        inner = jnp.sum(g * p, axis=0, keepdims=True)
        out = p * (g - inner + dlse_ref[...])
    # written token-major, as the two products of the router's backward
    # read it: whole 128 x 128 transposes, the experts padded with zeros
    if e % _LANES:
        out = jnp.concatenate(
            [out, jnp.zeros((-e % _LANES, tile), _F32)], axis=0)
    out_ref[...] = out.T[:, :e]


@functools.partial(jax.jit, static_argnames=_STATIC)
def _choice_bwd(logits, experts, dw, lse, dprob_sum, dlse, *, top_k,
                activation, tile, interpret):
    """-> d logits [T, E] float32, from the kept product and choice
    (and, for the softmax, ``lse`` and the two sums' cotangents)."""
    t, e = logits.shape
    block, rows, row, resident = _specs(e, top_k, tile)
    operands = [logits.T, experts.T, dw.astype(_F32).T]
    in_specs = [block, rows, rows]
    if activation == "softmax":
        operands += [lse[None], dprob_sum.astype(_F32)[:, None],
                     dlse.astype(_F32)[None]]
        in_specs += [row, resident(1), row]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, top_k=top_k, activation=activation),
        grid=(t // tile,), in_specs=in_specs,
        out_specs=pl.BlockSpec((tile, e), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, e), _F32),
        compiler_params=_compiler_params("parallel"),
        interpret=interpret)(*operands)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

class _Static(NamedTuple):
    """What the kernels are specialised on, besides their shapes."""
    top_k: int
    activation: str
    tile: int       # tokens a grid step
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _choice(logits, bias, static: _Static):
    return _choice_fwd(logits, bias, **static._asdict())


def _choice_vjp_fwd(logits, bias, static):
    weights, experts, counts, prob_sum, lse = _choice_fwd(
        logits, bias, **static._asdict())
    # before they part into primal and residuals, as the flash cores'
    experts = checkpoint_name(experts, ROUTER_EXPERTS)
    weights = checkpoint_name(weights, ROUTER_WEIGHTS)
    counts = checkpoint_name(counts, ROUTER_COUNTS)
    if static.activation == "softmax":
        lse = checkpoint_name(lse, ROUTER_LSE)
    return (weights, experts, counts, prob_sum, lse), (logits, experts, lse)


def _choice_vjp_bwd(static, res, cotangents):
    logits, experts, lse = res
    dw, _, _, dprob_sum, dlse = cotangents
    # none for the bias: it moves the choice alone
    return _choice_bwd(logits, experts, dw, lse, dprob_sum, dlse,
                       **static._asdict()), None


_choice.defvjp(_choice_vjp_fwd, _choice_vjp_bwd)


def router_choice(logits, select_bias=None, *, top_k: int,
                  activation: str = "softmax", interpret: bool = False):
    """The choice of a router whose float32 product is ``logits`` [T, E].

    ``activation``: ``"softmax"`` (the keys are the probabilities) or
    ``"sigmoid"`` (the keys are ``sigmoid(logits) + select_bias``, the
    weights the scores without it; ``select_bias`` [E], finite, takes
    no gradient). Returns ``(weights [T, k] float32, experts [T, k]
    int32, counts [E] int32, prob_sum [E], lse [T])``: the chosen
    scores in falling order of their keys, the lowest index first among
    equals; the routes each expert received; for the softmax the
    probabilities' sum over tokens and each token's ``logsumexp``
    (zeros for the sigmoid). Differentiable in ``logits`` through
    ``weights``, ``prob_sum`` and ``lse``."""
    t, e = logits.shape
    if activation not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown activation {activation!r}")
    if not shapes_ok(t, e, top_k):
        raise ValueError(
            f"the router's kernels do not tile {t} tokens, {e} experts, "
            f"top {top_k}")
    if activation == "sigmoid" and select_bias is None:
        select_bias = jnp.zeros((e,), _F32)
    return _choice(logits.astype(_F32),
                   select_bias if activation == "sigmoid" else None,
                   _Static(top_k, activation, _tile(t, e), interpret))

"""Mixture-of-Experts feed-forward layers. Two paths live here.

``routed_ffn`` is the one public models use: top-k, **dropless**.
The float32 router (its product in XLA at the highest precision; the
choice, the scores, the ``top_k``, the chosen weights, the counts and
the two sums, one Pallas kernel forward and one backward on a TPU,
``ops/pallas/router_choice.py``, PR 68; ``top_k``, a gather and
scatter-adds elsewhere), the ``tokens x k`` routes sorted by
expert, grouped matmuls over the ragged groups in bf16 with float32
accumulation, un-sort, weighted combine. No capacity, no dropped
route: every route to an expert held here is computed whatever the
skew. Where a share of the experts is held, a slab of the sorted
routes moves its rows without a scatter, in either pass
(``ops/pallas/route_rows.py``, PR 43): ``take_rows`` gathers the slab's
tokens' rows, ``sum_rows`` adds each token's weighted rows in float32
and writes the ``[t, d]`` once in the activations' dtype, on a TPU as a
gather into token order and a one-hot product on megablox ``tgmm`` that
reads the live rows alone. What runs through it, by argument:

- OLMoE-1B-7B (``models/llama.py``): the softmax router over 64
  experts, top-8 unnormalised, SwiGLU experts (three grouped matmuls),
  every expert held, with the load-balancing and z losses;
- Nemotron-3-Nano-30B-A3B (``models/nemotron_h.py``): the sigmoid
  router over 128 experts with its selection bias, top-6 renormalised
  and scaled, un-gated relu^2 experts (two grouped matmuls), and
  ``experts_held``: the layer is told which experts it holds (a chip's
  share under expert parallelism), routes over all of them and
  computes the part of the sum that its own give. The shared expert is
  the model's, a dense MLP beside this layer;
- JoyAI-LLM-Flash (``models/joyai.py``, the DeepSeek-V3 architecture):
  the same sigmoid router over 256 experts, top-8 renormalised and
  scaled, with **SwiGLU** experts and ``experts_held``: the slabs walk
  three grouped matmuls an expert (``_slab`` with a ``w_gate``), 16 of
  256 held in the benchmark's cell, a slab of 8,192 sorted rows;
- ZAYA1-8B (``models/zaya.py``) through ``routed_experts``, the form
  that takes its routes from the caller: that model's router is an MLP
  over a state the previous layer made, so it routes itself (top-1 of
  16, 8 held: a slab of all 16,384 rows) and hands in ``weights`` and
  ``experts``. ``routed_ffn`` is the same call with the routes made
  from a matrix first (``_routed`` is what the two share): the sort,
  the held share, the slabs, the grouped matmuls and the load are one
  code;
- SmallThinker-21B-A3B (``models/smallthinker.py``), also through
  ``routed_experts``: its router is this file's softmax router
  (``route_softmax``: top-6 of 64, renormalised) but reads the block's
  input before attention, and its experts are **ReGLU** (``expert=
  "reglu"``: ``relu`` where SwiGLU has ``silu``), 16 of 64 held.

On a mesh that shards tokens (dp, fsdp, sp) each chip routes and sorts
its own tokens under ``shard_map`` with the experts replicated; a mesh
that would shard the experts (``ep > 1``, ``tp > 1``) is refused: the
exchange of routes between chips is not implemented yet.

``top1_dispatch`` / ``moe_ffn`` / ``dense_switch_ffn_reference`` are
the older switch path (``models/moe.py``): top-1, capacity-dropping,
dense one-hot ``[T, E, C]`` dispatch/combine einsums, experts sharded
over ``ep`` with ``lax.all_to_all``. No public model computes that,
and its one-hot tensors cannot exist at real sizes; it is on its way
out (ROADMAP C5) and goes when the dropless path runs under ``ep``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.pallas import program, route_rows, router_choice
from ray_tpu.ops.remat import (
    ROUTER_COUNTS, ROUTER_EXPERTS, ROUTER_LOGITS, ROUTER_WEIGHTS)
from ray_tpu.util import tracing


def top1_dispatch(router_logits: jnp.ndarray, num_experts: int,
                  capacity: int):
    """Build switch-routing dispatch/combine tensors.

    router_logits: [T, E]. Returns (dispatch [T, E, C] bool-ish float,
    combine [T, E, C] float, aux_loss scalar).
    Tokens beyond an expert's capacity are dropped (standard switch
    behavior); aux_loss is the load-balancing loss.
    """
    probs = jax.nn.softmax(router_logits, axis=-1)          # [T, E]
    expert_idx = jnp.argmax(probs, axis=-1)                 # [T]
    expert_mask = jax.nn.one_hot(expert_idx, num_experts)   # [T, E]
    # Position of each token within its expert's queue.
    position = jnp.cumsum(expert_mask, axis=0) * expert_mask - 1.0
    in_capacity = (position < capacity) & (expert_mask > 0)
    pos_clipped = jnp.clip(position, 0, capacity - 1).astype(jnp.int32)
    pos_onehot = jax.nn.one_hot(pos_clipped, capacity)      # [T, E, C]
    dispatch = pos_onehot * in_capacity[..., None]
    gate = jnp.max(probs * expert_mask, axis=-1)            # [T]
    combine = dispatch * gate[:, None, None]
    # Load-balance aux loss (Switch Transformer eq. 4).
    density = expert_mask.mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux = num_experts * jnp.sum(density * density_proxy)
    return dispatch, combine, aux


def moe_ffn(x, router_w, w_up, w_down, axis: str = "ep",
            capacity_factor: float = 2.0):
    """Expert-parallel switch FFN; call inside shard_map.

    x:        [T, D]   local tokens (token dim NOT sharded on ep here;
                        each ep rank routes its own tokens)
    router_w: [D, E]   replicated
    w_up:     [E_local, D, H] local experts (expert dim sharded on ep)
    w_down:   [E_local, H, D]
    Returns (y [T, D], aux_loss).
    """
    ep = lax.psum(1, axis)
    e_local = w_up.shape[0]
    num_experts = e_local * ep
    t = x.shape[0]
    capacity = max(1, int(capacity_factor * t / num_experts))

    logits = x @ router_w                                   # [T, E]
    dispatch, combine, aux = top1_dispatch(logits, num_experts,
                                           capacity)
    d = x.shape[-1]
    # Dispatch tokens to expert queues: [E, C, D].
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
    # Exchange over ep. [E, C, D] -> [ep_dst, e_local, C, D]; piece i
    # goes to rank i; received pieces stack as a new leading source-
    # rank dim: [ep_src, e_local, C, D].
    expert_in = expert_in.reshape(ep, e_local, capacity, d)
    expert_in = lax.all_to_all(expert_in, axis, split_axis=0,
                               concat_axis=0, tiled=False)
    # Each local expert processes the queues from every source rank.
    expert_in = jnp.moveaxis(expert_in, 0, 1).reshape(
        e_local, ep * capacity, d)

    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, w_up))
    out = jnp.einsum("ech,ehd->ecd", h, w_down)

    # Route back: regroup by source rank and apply the inverse
    # exchange (all_to_all with the same specs is an involution here).
    out = out.reshape(e_local, ep, capacity, d)
    out = jnp.moveaxis(out, 1, 0)                  # [ep_src, e_local, C, D]
    out = lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                         tiled=False)              # [ep_owner, e_local, C, D]
    out = out.reshape(num_experts, capacity, d)    # [E, C, D]
    y = jnp.einsum("tec,ecd->td", combine, out)
    return y, aux


def dense_switch_ffn_reference(x, router_w, w_up_full, w_down_full,
                               capacity_factor: float = 2.0):
    """Single-device reference for tests: same math, no all_to_all.
    w_*_full carry ALL experts."""
    num_experts = w_up_full.shape[0]
    t = x.shape[0]
    capacity = max(1, int(capacity_factor * t / num_experts))
    logits = x @ router_w
    dispatch, combine, aux = top1_dispatch(logits, num_experts,
                                           capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, w_up_full))
    out = jnp.einsum("ech,ehd->ecd", h, w_down_full)
    y = jnp.einsum("tec,ecd->td", combine, out)
    return y, aux


# --------------------------------------------------------------------------
# The dropless top-k path
# --------------------------------------------------------------------------

def grouped_matmul_path() -> str:
    """Which grouped matmul ``routed_ffn`` compiles on this backend:
    the megablox ``gmm`` Pallas kernel on a TPU, ``lax.ragged_dot``
    elsewhere (chosen from the backend, as ``causal_attention``
    chooses its kernel)."""
    return "megablox_gmm" if jax.default_backend() == "tpu" else "ragged_dot"


# (m, k, n) tile of the megablox kernel: rows of routed tokens, the
# contracted width, the output width. Chosen on the v5e (PERF.md 6).
_GMM_TILING = (512, 1024, 1024)


@functools.lru_cache(maxsize=None)
def _gmm_tiling(rows_per_expert: int):
    """The (m, k, n) tile for each grouped matmul of a layer, forward
    and backward, from that matmul's own shapes: ``_GMM_TILING``, but
    the row tile no larger than the rows an expert is expected to own
    (a group's last tile is computed whole, and a tile that straddles
    two groups twice: at 384 rows an expert a 512-row tile is more
    padding than work), and a width tile that pads its dimension least
    (2,688 = 3 x 896; 1,856 into 3 x 640 rather than 2 x 1,024). One
    function a hint, so that a step's layers share their kernels'
    jitted functions."""
    tm = min(_GMM_TILING[0], max(128, 1 << (rows_per_expert.bit_length() - 1)))
    width = route_rows.width_tile

    def tiles(m: int, k: int, n: int) -> tuple[int, int, int]:
        return (min(tm, m), min(width(k, _GMM_TILING[1]), k),
                min(width(n, _GMM_TILING[2]), n))
    return tiles


def _grouped_matmul(lhs, rhs, group_sizes, rows_per_expert: int):
    """``[m, k] x [E, k, n] -> [m, n]``: rows ``lhs`` sorted by group,
    group ``e`` holding ``group_sizes[e]`` of them, each multiplied by
    its group's matrix. bf16 in and out, float32 accumulation."""
    if grouped_matmul_path() == "megablox_gmm":
        from jax.experimental.pallas.ops.tpu.megablox import ops
        return ops.gmm(lhs, rhs, group_sizes, lhs.dtype,
                       _gmm_tiling(rows_per_expert))
    return lax.ragged_dot(lhs, rhs, group_sizes,
                          preferred_element_type=jnp.float32
                          ).astype(lhs.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inverse, k):
    """Row ``order[i] // k`` of ``x`` for every sorted route ``i``. The
    backward is a gather too (by the inverse permutation, then a sum
    over each token's ``k`` routes), never a scatter."""
    return x[order // k]


def _dispatch_fwd(x, order, inverse, k):
    return x[order // k], inverse


def _dispatch_bwd(k, inverse, g):
    return g[inverse].reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(ys, order, inverse):
    """Sorted routes back in token order: ``ys[inverse]``; backward
    ``g[order]``."""
    return ys[inverse]


def _unsort_fwd(ys, order, inverse):
    return ys[inverse], order


def _unsort_bwd(order, g):
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def _logits(x, router_w):
    """A router's float32 product ``x W`` at the highest precision,
    under ``ROUTER_LOGITS``: a recomputed block whose policy lists the
    name runs the float32 matmul once."""
    return checkpoint_name(
        jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                precision=lax.Precision.HIGHEST), ROUTER_LOGITS)


def _route(x, router_w, top_k: int, norm_topk_prob: bool, path: str = "xla"):
    """float32 softmax router of ``[T, d]`` tokens: (weights [T, k],
    experts [T, k], sum over tokens of the probabilities [E], sum over
    tokens of logsumexp(logits)^2, the routes each expert received [E]
    or None where the caller counts them). ``path``
    (``router_choice.router_path``'s word) says who chooses between the
    product and those: ``"pallas"`` the kernel pair of
    ``ops/pallas/router_choice.py`` (``"interpret"``: the same,
    interpreted), which makes the probabilities, the ``top_k``, the
    counts and both sums in one pass over the product and its backward
    in one more, and names its choice, chosen probabilities, counts and
    ``lse`` (``ROUTER_KEEPS``), so that a recomputed block that keeps
    them runs neither the kernel nor a ``top_k`` again; ``"xla"``
    today's lines for the CPU, shapes that do not tile and one program
    over several devices: the chosen probabilities are ``top_k``'s own
    values, differentiated through its own indices, which no name
    reaches, so there a recomputed block runs ``top_k`` twice (taking
    them by a gather with a named choice costs as much again at 512
    experts: 1.7 ms a layer for the gather and for its scatter-add,
    PERF.md section 6, PR 67)."""
    logits = _logits(x, router_w)
    if path != "xla":
        weights, experts, counts, prob_sum, lse = router_choice.router_choice(
            logits, top_k=top_k, activation="softmax",
            interpret=path == "interpret")
    else:
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        probs = jnp.exp(logits - lse[:, None])
        weights, experts = lax.top_k(probs, top_k)
        counts, prob_sum = None, probs.sum(axis=0)
    if norm_topk_prob:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return weights, experts, prob_sum, jnp.sum(lse * lse), counts


def _route_sigmoid(x, router_w, select_bias, top_k: int,
                   norm_topk_prob: bool, route_scale: float,
                   path: str = "xla"):
    """The float32 sigmoid router (DeepSeek-V3's, Nemotron-H's): every
    expert scored ``s = sigmoid(x . W)`` on its own; the ``top_k`` are
    chosen by ``s + select_bias`` (the score-correction bias: it moves
    the choice and carries no gradient), their weights are ``s``
    **without** it, divided by their sum (+1e-20) under
    ``norm_topk_prob``, times ``route_scale``. No auxiliary loss
    belongs to it: the two sums are zeros. ``path`` as ``_route``'s:
    the kernel pair, or ``top_k`` and a gather of the chosen scores.
    The product, the choice and the chosen scores carry
    ``ROUTER_KEEPS``'s names on either, for a recomputed block whose
    policy lists them."""
    logits = _logits(x, router_w)
    if path != "xla":
        weights, experts, counts, _, _ = router_choice.router_choice(
            logits, select_bias, top_k=top_k, activation="sigmoid",
            interpret=path == "interpret")
    else:
        scores = jax.nn.sigmoid(logits)
        _, experts = lax.top_k(
            scores + lax.stop_gradient(select_bias.astype(jnp.float32)),
            top_k)
        experts = checkpoint_name(experts, ROUTER_EXPERTS)
        weights = checkpoint_name(
            jnp.take_along_axis(scores, experts, axis=-1), ROUTER_WEIGHTS)
        counts = None
    if norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return (weights * route_scale, experts,
            jnp.zeros((router_w.shape[-1],), jnp.float32), jnp.float32(0),
            counts)


_GATES = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}


def _experts(xs, w_gate, w_up, w_down, counts, rows_per_expert: int,
             kind: str = "swiglu"):
    """The experts on rows sorted by expert, ``counts[e]`` of them for
    expert ``e`` (``rows_per_expert`` at an even load): gated (three
    grouped matmuls; ``kind`` names the gate's activation: SwiGLU's
    ``silu`` or ReGLU's ``relu``, whose backward is a mask), or with no
    ``w_gate`` the un-gated relu^2 expert (two)."""
    dt = xs.dtype
    gmm = functools.partial(_grouped_matmul, group_sizes=counts,
                            rows_per_expert=rows_per_expert)
    if w_gate is None:
        hidden = jnp.square(jax.nn.relu(gmm(xs, w_up.astype(dt))))
    else:
        gate = gmm(xs, w_gate.astype(dt))
        up = gmm(xs, w_up.astype(dt))
        hidden = _GATES[kind](gate) * up
    return gmm(hidden, w_down.astype(dt))


_HELD_ROOM = 2       # times the even share: the rows gathered for a share


def held_rows(routes: int, held: int, experts: int) -> int:
    """How many sorted rows a layer that holds ``held`` of ``experts``
    experts gathers and multiplies at a time (a slab; one is enough
    unless more routes than that land on them): ``_HELD_ROOM`` times
    the even share of the ``routes``, rounded up to the grouped
    matmul's row tile, and never more than all of them. The gather
    (``take_rows``) moves every row of the slab, 25 ns a row on a v5e;
    the grouped matmuls and ``sum_rows`` read only the rows whose route
    landed here."""
    tile = _GMM_TILING[0]
    even = routes * held / experts
    return min(routes, tile * math.ceil(_HELD_ROOM * even / tile))


def _slab(lo, static, out, x, order, pos, weights, sizes, w_gate, w_up,
          w_down):
    """The sorted routes ``lo .. lo + rows`` (``order`` holds the held
    experts' routes first, by expert, ``sizes[e]`` of them for held
    expert ``e``; ``pos`` [t, k] is its inverse): their tokens' rows
    taken (``route_rows.take_rows``: a gather, zero past the live ones),
    multiplied by the experts that own them, and each token's rows
    weighted and added up in float32 (``route_rows.sum_rows``: no
    scatter, and no row past the live ones is read), ``[t, d]`` in the
    dtype ``out``. ``lo`` may be traced; ``static`` is (the slab's
    rows, top_k, an expert's rows at an even load, the experts' kind,
    ``route_rows.rows_path``'s word)."""
    rows, top_k, per_expert, kind, path = static
    with jax.named_scope("dispatch"):
        ends = jnp.cumsum(sizes)
        own = (jnp.clip(ends, lo, lo + rows)
               - jnp.clip(ends - sizes, lo, lo + rows))
        slab = route_rows.Slab(lax.dynamic_slice(order, (lo,), (rows,)),
                               pos, lo, ends[-1])
        xs = route_rows.take_rows(x, slab, path)
    with jax.named_scope("experts"):
        # rows past the real ones hold whatever the kernel left
        ys = _experts(xs, w_gate, w_up, w_down, own, per_expert, kind)
    with jax.named_scope("combine"):
        return route_rows.sum_rows(ys, weights, slab, path, out)


def _slabs_needed(sizes, rows):
    return (sizes.sum() + rows - 1) // rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _slabs(static, x, order, pos, weights, sizes, w_gate, w_up, w_down):
    """Every slab of ``rows`` sorted routes that holds a real one,
    added up, in ``x``'s dtype. The first slab's float32 sums are cast
    and written once, and where it holds every route that landed here
    (twice the even share or less) that is the answer; else all the
    slabs, the first again, are walked in a loop that runs as often as
    the routes need and adds their sums in float32, on the plain row
    path (``_overflow``; the first stays
    outside the conditional so that its scopes read ``mlp/experts``,
    not ``mlp/cond/...``). The backward walks the same slabs,
    recomputing each (the gathered rows and the experts' hidden
    activations: 1% of a Nemotron step's operations), so nothing of a
    slab outlives it."""
    args = (x, order, pos, weights, sizes, w_gate, w_up, w_down)
    rows = static[0]
    first = _slab(0, static, x.dtype, *args)
    if rows == order.shape[0]:      # the slab holds every route there is
        return first
    return lax.cond(
        _slabs_needed(sizes, rows) <= 1,
        lambda: first,
        lambda: lax.fori_loop(
            0, _slabs_needed(sizes, rows),
            lambda j, y: y + _slab(j * rows, _overflow(static), jnp.float32,
                                   *args),
            jnp.zeros(x.shape, jnp.float32)).astype(x.dtype))


def _overflow(static):
    """``static`` for the slabs of the overflow loops: their sums on the
    plain row path. A step that never overflows pays for those loops'
    kernels in its set-up alone (each sum a sort and a ``tgmm``: 6 s of
    the SmallThinker cell's 50, PERF.md section 6, PR 43), and the
    gathers by ``pos`` are exact too."""
    return static[:-1] + ("xla",)


def _slabs_fwd(static, *args):
    return _slabs(static, *args), args


def _slabs_bwd(static, args, dy):
    x, order, pos, weights, sizes, *ws = args
    rows = static[0]

    def pull(lo, static):
        return jax.vjp(
            lambda x, weights, *ws: _slab(lo, static, x.dtype, x, order, pos,
                                          weights, sizes, *ws),
            x, weights, *ws)[1](dy)

    dx, dweights, *dws = pull(0, static)
    if rows < order.shape[0]:
        dx, dweights, *dws = lax.fori_loop(
            1, _slabs_needed(sizes, rows),
            lambda j, acc: jax.tree_util.tree_map(
                jnp.add, acc, pull(j * rows, _overflow(static))),
            (dx, dweights, *dws))
    return (dx, None, None, dweights, None, *dws)


_slabs.defvjp(_slabs_fwd, _slabs_bwd)


def _held_part(x, flat, weights, counts, w_gate, w_up, w_down, top_k,
               experts_held, kind, rows_path="xla"):
    """The held experts' part of every token's sum, ``[t, d]``. The
    held experts' routes sort to the front (by expert; every absent
    expert's route takes one key behind them), and the sorted routes
    are walked in slabs of ``held_rows`` rows (``_slabs``): no route is
    ever dropped and no buffer has the worst case's ``t * top_k``
    rows."""
    first, held = experts_held
    routes = x.shape[0] * top_k
    rows = held_rows(routes, held, counts.shape[0])
    with jax.named_scope("dispatch"):
        local = flat - first
        key = jnp.where((local >= 0) & (local < held), local, held)
        iota = jnp.arange(routes, dtype=jnp.int32)
        _, order = lax.sort((key, iota), num_keys=1, is_stable=True)
        _, pos = lax.sort((order, iota), num_keys=1)
        # whole slabs: the rows past the routes are never live
        order = jnp.pad(order, (0, -routes % rows))
    return _slabs((rows, top_k, max(1, routes // counts.shape[0]), kind,
                   rows_path), x, order,
                  pos.reshape(-1, top_k), weights,
                  counts[first:first + held], w_gate, w_up, w_down)


def _given(x, weights, experts, path: str = "xla"):
    """The routes a caller made (``routed_experts``), a row a token;
    no auxiliary loss belongs to them here (the two sums are zeros),
    and their counts are the layer's to make."""
    k = weights.shape[-1]
    return (weights.reshape(-1, k).astype(jnp.float32),
            experts.reshape(-1, k), jnp.float32(0), jnp.float32(0), None)


def _routed_ffn_local(x, route_args, w_gate, w_up, w_down, *, route,
                      num_experts, top_k, over=(), experts_held=None,
                      kind="swiglu", rows_path="xla", router_path="xla"):
    """The layer on the tokens in hand (``[..., d]``), their routes
    made by ``route(x, *route_args, path=router_path)`` (``_route``,
    ``_route_sigmoid`` or ``_given``; the routes each expert received
    are the router's kernel's, or where it gives none a scatter-add of
    ``T k`` ones here); the router's sums are added over the mesh axes
    ``over`` so that the two losses and the load are those of the
    global batch. ``w_gate`` None: the two-matrix relu^2 expert; else
    ``kind`` is the gated expert's (``_experts``)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    t, e = x.shape[0], num_experts
    with jax.named_scope("router"):
        weights, experts, prob_sum, z_sum, counts = route(
            x, *route_args, path=router_path)
        flat = experts.reshape(-1)
        if counts is None:
            counts = checkpoint_name(
                jnp.zeros((e,), jnp.int32).at[flat].add(1), ROUTER_COUNTS)
        total = (counts.astype(jnp.float32), prob_sum, z_sum,
                 jnp.float32(t))
        if over:
            total = lax.psum(total, over)
        load, prob_sum, z_sum, n = total
        # E * sum_e f_e P_e: f_e the routes to e over the tokens (it
        # sums to k), P_e the mean router probability of e.
        aux = e * jnp.sum(load / n * prob_sum / n)
        z = z_sum / n
    if experts_held is not None:
        y = _held_part(x, flat, weights, counts, w_gate, w_up, w_down,
                       top_k, experts_held, kind, rows_path)
        return y.reshape(shape), aux, z, load
    with jax.named_scope("dispatch"):
        iota = jnp.arange(t * top_k, dtype=jnp.int32)
        _, order = lax.sort((flat, iota), num_keys=1, is_stable=True)
        _, inverse = lax.sort((order, iota), num_keys=1)
        xs = _dispatch(x, order, inverse, top_k)
    with jax.named_scope("experts"):
        ys = _experts(xs, w_gate, w_up, w_down, counts,
                      t * top_k // e, kind)
    with jax.named_scope("combine"):
        dt = x.dtype
        ys = _unsort(ys, order, inverse).reshape(t, top_k, -1)
        y = jnp.einsum("tkd,tk->td", ys, weights.astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
    return y.reshape(shape), aux, z, load


def routed_ffn(x, router_w, w_gate, w_up, w_down, *, top_k: int,
               norm_topk_prob: bool = False, mesh=None,
               router: str = "softmax", select_bias=None,
               route_scale: float = 1.0, expert: str = "swiglu",
               experts_held: tuple[int, int] | None = None):
    """Dropless top-k mixture of experts.

    x:        [batch, seq, d] (or [tokens, d]) activations
    router_w: [d, E]          bias-free router, over **all** E experts
    w_gate, w_up: [E_held, d, f];  w_down: [E_held, f, d]

    ``router``: ``"softmax"`` (OLMoE: probabilities over all experts,
    the ``top_k`` largest, renormalised only with ``norm_topk_prob``)
    or ``"sigmoid"`` (Nemotron-H, DeepSeek-V3: ``_route_sigmoid``, with
    ``select_bias`` [E] and ``route_scale``). Either chooses on the
    path ``router_choice.router_path`` names for a chip's tokens: the
    kernel pair of ``ops/pallas/router_choice.py`` on a TPU where the
    shapes tile (inside the ``shard_map`` below a shard at a time),
    XLA's ``top_k``, gather and scatter-adds on the CPU, for shapes
    that do not tile and where the layer is one global program over
    several devices; the same routes, weights and gradients on both,
    said on the trace span as ``moe_router_path``. ``expert``: ``"swiglu"``
    (three matrices, ``down(silu(gate x) * up x)``), ``"reglu"`` (the
    same three with ``relu`` on the gate: SmallThinker's) or ``"relu2"``
    (two, ``down(relu(up x)^2)``; ``w_gate`` is None). ``experts_held =
    (first, count)``: this caller holds experts ``first .. first +
    count - 1`` of the ``E`` the router scores (one chip's share under
    expert parallelism; default: all). The router still sees every
    token and every expert; routes to absent experts are sorted behind
    the held ones' and are neither gathered nor multiplied:
    ``held_rows`` (twice the even share) sorted rows are, with the
    group sizes saying how many are real, and further slabs of as many
    only when more than that land here (``_slabs``). A slab's rows come
    by a gather and go back by a gather and a product, never a
    scatter-add (``route_rows.take_rows`` / ``sum_rows``; float32
    sums, written once in ``x``'s dtype). ``y`` is then the held
    experts' part of each token's sum; what the absent experts would
    add is left to the chips that hold them.

    Returns ``(y, aux, z, load)``: the output in ``x``'s shape and
    dtype (sum over each token's ``top_k`` experts of router weight
    times the expert's output) and, in float32, the load-balancing
    loss ``E * sum_e f_e P_e`` (the published code's form: ``f_e`` sums
    to ``top_k``), the router z-loss ``mean(logsumexp(logits)^2)``
    (both zero for the sigmoid router, which has neither) and the
    routes each of the ``E`` experts received, ``[E]``, summing to
    ``tokens * top_k`` also when only a share is held
    (``held_route_share`` reads the share's part of it).

    On a ``mesh`` that shards tokens (dp, fsdp on the batch, sp on the
    sequence) each chip routes, sorts and computes the tokens it holds
    under ``shard_map``, experts replicated; the three returned
    statistics are those of the global batch. A sort over a dimension
    sharded over ``dp`` would gather every token to every chip.
    ``ep > 1`` and ``tp > 1`` raise ``NotImplementedError``:
    ``experts_held`` is what a chip of an ``ep`` mesh will be told,
    the exchange of routes between them is not written.
    """
    if router not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown router {router!r}")
    _check_kind(expert, w_gate)
    e = router_w.shape[-1]
    if router == "sigmoid":
        route = functools.partial(
            _route_sigmoid, top_k=top_k, norm_topk_prob=norm_topk_prob,
            route_scale=route_scale)
        route_args = (router_w, jnp.zeros((e,), jnp.float32)
                      if select_bias is None else select_bias)
    else:
        route = functools.partial(_route, top_k=top_k,
                                  norm_topk_prob=norm_topk_prob)
        route_args = (router_w,)
    said = {}
    if (router, expert, experts_held) != ("softmax", "swiglu", None):
        # beside today's keys, and only where one of them says something
        said = dict(moe_router=router, moe_expert_kind=expert)
    return _routed(x, route, route_args, False, w_gate, w_up, w_down,
                   num_experts=e, top_k=top_k, mesh=mesh,
                   experts_held=experts_held, kind=expert, said=said)


def _check_kind(expert: str, w_gate) -> None:
    if (expert == "relu2") != (w_gate is None) or expert not in (
            "swiglu", "reglu", "relu2"):
        raise ValueError(f"expert {expert!r} with w_gate "
                         f"{'absent' if w_gate is None else 'given'}")


def routed_experts(x, weights, experts, w_gate, w_up, w_down, *,
                   num_experts: int, mesh=None,
                   experts_held: tuple[int, int] | None = None,
                   expert: str | None = None):
    """``routed_ffn`` for a caller that makes the routes itself (a
    router that is not one matrix: ``models/zaya.py``'s is an MLP over
    a state the previous layer made).

    weights: [batch, seq, k] (or [tokens, k])  each token's ``k`` router
                              weights, float32, carrying the gradient
    experts: [batch, seq, k]  the experts they belong to, of
                              ``num_experts``
    ``x``, the experts' matrices, ``mesh`` and ``experts_held`` as
    ``routed_ffn``; ``expert`` its kinds too (default: ``"relu2"``
    without a ``w_gate``, else ``"swiglu"``). The sort, the slabs and
    the grouped matmuls are the same code. Returns ``(y, load)``: the
    held experts' part of each token's sum and the routes each of the
    ``num_experts`` experts received, ``[E]`` float32. Where the
    caller's router is one matrix it can make its routes with
    ``route_softmax``, this file's router, from another place than the
    experts' input (SmallThinker's reads the block's input, before
    attention)."""
    expert = expert or ("relu2" if w_gate is None else "swiglu")
    _check_kind(expert, w_gate)
    y, _, _, load = _routed(
        x, _given, (weights, experts), True, w_gate, w_up, w_down,
        num_experts=num_experts, top_k=weights.shape[-1], mesh=mesh,
        experts_held=experts_held, kind=expert,
        said=dict(moe_router="caller", moe_expert_kind=expert))
    return y, load


def route_softmax(x, router_w, *, top_k: int, norm_topk_prob: bool = False,
                  mesh=None):
    """``routed_ffn``'s softmax router for a caller that routes from
    another place than its experts' input: ``x`` [..., d] against the
    bias-free ``router_w`` [d, E] in float32 at the highest precision,
    the ``top_k`` largest probabilities, renormalised over the chosen
    under ``norm_topk_prob`` (which is a softmax over the chosen logits
    alone). Returns ``(weights [..., k], experts [..., k])`` for
    ``routed_experts``; a router is a few values a token, so each chip
    routes the tokens it holds under any sharding of them: on a
    ``mesh`` that shards tokens the choice's kernel pair runs a shard
    at a time under ``shard_map``, as ``routed_ffn``'s, and the plain
    lines stay one global program. The path is said on the trace span
    as ``moe_router_path``."""
    shards = _token_shards(mesh, x)
    path = ("xla" if shards.one_program else router_choice.router_path(
        shards.tokens, router_w.shape[-1], top_k))

    def local(x, router_w):
        weights, experts, *_ = _route(x.reshape(-1, x.shape[-1]), router_w,
                                      top_k, norm_topk_prob, path)
        return (weights.reshape(*x.shape[:-1], top_k),
                experts.reshape(*x.shape[:-1], top_k))

    if shards.axes and path != "xla":
        from jax.sharding import PartitionSpec as P
        local = jax.shard_map(
            local, mesh=mesh, in_specs=(shards.spec, P()),
            out_specs=(shards.spec, shards.spec), check_vma=False)
    tracing.note_trace(moe_router_path=path)
    return local(x, router_w)


class _Shards(NamedTuple):
    """How a mesh shards a ``[batch, seq, d]`` (or ``[tokens, d]``)
    activation's tokens: the axes of ``program.token_axes`` in one
    tuple, the activation's ``PartitionSpec`` over them, a chip's
    tokens, and whether the layer is one global program over several
    devices (no axis shards the tokens of a mesh of more than one),
    where the plain forms run: a bare ``pallas_call`` has no SPMD
    rule."""
    axes: tuple
    spec: Any
    tokens: int
    one_program: bool


def _token_shards(mesh, x) -> _Shards:
    """The layer replicates its experts on every chip, so a mesh that
    shards them (ep, tp) is refused by name."""
    from jax.sharding import PartitionSpec as P
    program.refuse(
        mesh, "routed_ffn",
        ep="expert parallelism of the dropless top-k path (an all_to_all "
           "of the sorted routes: as it is the layer replicates its "
           "experts and sorts each chip's own tokens, and the shard_map "
           "would gather every expert onto every chip each step; the "
           "top-1 moe_ffn / SwitchFFN still run under ep)",
        tp="tensor parallelism of the dropless top-k path (each expert's "
           "width split over the axis: as it is the layer replicates its "
           "experts)")
    batch_axes, seq_axis = program.token_axes(
        mesh, x.shape[0], x.shape[1] if x.ndim == 3 else 1)
    axes = batch_axes + ((seq_axis,) if seq_axis else ())
    return _Shards(axes, P(batch_axes or None, seq_axis),
                   math.prod(x.shape[:-1]) // math.prod(
                       mesh.shape[a] for a in axes),
                   not axes and mesh is not None and mesh.size > 1)


def _routed(x, route, route_args, per_token: bool, w_gate, w_up, w_down, *,
            num_experts, top_k, mesh, experts_held, said, kind="swiglu"):
    """What the two public forms share: the checks, ``shard_map`` over
    the axes that shard tokens (``route_args`` enter sharded like the
    tokens where they are ``per_token``, replicated where they are the
    router's weights), the notes on the trace span."""
    e = num_experts
    first, held = experts_held or (0, e)
    if not (0 <= first and first + held <= e and w_up.shape[0] == held):
        raise ValueError(f"experts_held {experts_held} of {e} experts, "
                         f"weights for {w_up.shape[0]}")
    axes, held_spec, tokens, one_program = _token_shards(mesh, x)
    rows = held_rows(tokens * top_k, held, e)
    rows_path = ("xla" if one_program
                 else route_rows.rows_path(tokens, rows))
    # a caller's routes (``per_token``) were chosen elsewhere
    router_path = ("xla" if one_program or per_token
                   else router_choice.router_path(tokens, e, top_k))
    local = functools.partial(
        _routed_ffn_local, route=route, num_experts=e, top_k=top_k,
        experts_held=experts_held, kind=kind, rows_path=rows_path,
        router_path=router_path)
    if axes:
        from jax.sharding import PartitionSpec as P
        # All mesh axes manual, as in ops/attention.py and the chunked
        # cross-entropy: the weights enter replicated, so the transpose
        # sums their gradients over the axes once.
        out = jax.shard_map(
            functools.partial(local, over=axes), mesh=mesh,
            in_specs=(held_spec,
                      tuple(held_spec if per_token else P()
                            for _ in route_args),
                      None if w_gate is None else P(), P(), P()),
            out_specs=(held_spec, P(), P(), P()), check_vma=False)(
                x, route_args, w_gate, w_up, w_down)
    else:
        out = local(x, route_args, w_gate, w_up, w_down)
    notes = dict(
        moe_tokens=tokens, moe_experts=e, moe_top_k=top_k,
        moe_routes=tokens * top_k, moe_path=grouped_matmul_path(),
        moe_axes=list(axes))
    if said:
        notes.update(
            said, moe_experts_held=[first, held], moe_rows_sorted=rows)
        if experts_held is not None:
            notes.update(moe_rows_path=rows_path)
    if not per_token:
        notes.update(moe_router_path=router_path)
    tracing.note_trace(**notes)
    return out


def held_route_share(load, experts_held: tuple[int, int]):
    """Of all the routes in ``load`` ([..., E], as ``routed_ffn``
    returns it), the share that landed on the experts held."""
    first, held = experts_held
    return load[..., first:first + held].sum() / load.sum()

"""Mixture-of-Experts feed-forward layers. Two paths live here.

``routed_ffn`` is the one public models use (OLMoE-1B-7B through
``models/llama.py``): top-k, **dropless**. Router logits and softmax in
float32, ``top_k``, the ``tokens x k`` routes sorted by expert, three
grouped matmuls (SwiGLU) over the ragged groups in bf16 with float32
accumulation, un-sort, weighted combine. No capacity, no dropped
route: every one of the ``T*k`` routes is computed whatever the skew.
On a mesh that shards tokens (dp, fsdp, sp) each chip routes and sorts
its own tokens under ``shard_map`` with the experts replicated; a mesh
that would shard the experts (``ep > 1``, ``tp > 1``) is refused: not
implemented for this path yet.

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

import jax
import jax.numpy as jnp
from jax import lax

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


def _grouped_matmul(lhs, rhs, group_sizes):
    """``[m, k] x [E, k, n] -> [m, n]``: rows ``lhs`` sorted by group,
    group ``e`` holding ``group_sizes[e]`` of them, each multiplied by
    its group's matrix. bf16 in and out, float32 accumulation."""
    if grouped_matmul_path() == "megablox_gmm":
        from jax.experimental.pallas.ops.tpu.megablox import ops
        m, k = lhs.shape
        n = rhs.shape[-1]
        tiling = tuple(min(t, s) for t, s in zip(_GMM_TILING, (m, k, n)))
        return ops.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling)
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


def _route(x, router_w, top_k: int, norm_topk_prob: bool):
    """float32 router of ``[T, d]`` tokens: (weights [T, k], experts
    [T, k], sum over tokens of the probabilities [E], sum over tokens
    of logsumexp(logits)^2)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    weights, experts = lax.top_k(probs, top_k)
    if norm_topk_prob:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return weights, experts, probs.sum(axis=0), jnp.sum(lse * lse)


def _routed_ffn_local(x, router_w, w_gate, w_up, w_down, *, top_k,
                      norm_topk_prob, over=()):
    """The layer on the tokens in hand (``[..., d]``); the router's
    sums are added over the mesh axes ``over`` so that the two losses
    and the load are those of the global batch."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    t, e = x.shape[0], router_w.shape[-1]
    with jax.named_scope("router"):
        weights, experts, prob_sum, z_sum = _route(
            x, router_w, top_k, norm_topk_prob)
        flat = experts.reshape(-1)
        counts = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        total = (counts.astype(jnp.float32), prob_sum, z_sum,
                 jnp.float32(t))
        if over:
            total = lax.psum(total, over)
        load, prob_sum, z_sum, n = total
        # E * sum_e f_e P_e: f_e the routes to e over the tokens (it
        # sums to k), P_e the mean router probability of e.
        aux = e * jnp.sum(load / n * prob_sum / n)
        z = z_sum / n
    with jax.named_scope("dispatch"):
        iota = jnp.arange(t * top_k, dtype=jnp.int32)
        _, order = lax.sort((flat, iota), num_keys=1, is_stable=True)
        _, inverse = lax.sort((order, iota), num_keys=1)
        xs = _dispatch(x, order, inverse, top_k)
    with jax.named_scope("experts"):
        dt = x.dtype
        gate = _grouped_matmul(xs, w_gate.astype(dt), counts)
        up = _grouped_matmul(xs, w_up.astype(dt), counts)
        ys = _grouped_matmul(jax.nn.silu(gate) * up, w_down.astype(dt),
                             counts)
    with jax.named_scope("combine"):
        ys = _unsort(ys, order, inverse).reshape(t, top_k, -1)
        y = jnp.einsum("tkd,tk->td", ys, weights.astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
    return y.reshape(shape), aux, z, load


def _token_axes(mesh, batch: int, seq: int):
    """The mesh axes of size > 1 that shard a ``[batch, seq, d]``
    activation's tokens, as ``train.step.batch_spec`` places them:
    (dp and fsdp on the batch, sp or None on the sequence). ((), None)
    where the layer stays one global program: no mesh, one device, or
    shapes the axes do not divide (the tiny batch of init-time
    tracing). The layer replicates its experts on every chip, so a
    mesh that shards them (ep, tp) is refused by name."""
    if mesh is None or mesh.size == 1:
        return (), None
    from ray_tpu.parallel.mesh import (
        AXIS_DP, AXIS_EP, AXIS_FSDP, AXIS_SP, AXIS_TP)
    for axis, what in ((AXIS_EP, "expert parallelism (an all_to_all of "
                        "the sorted routes)"),
                       (AXIS_TP, "tensor parallelism (each expert's "
                        "width split over the axis)")):
        if mesh.shape.get(axis, 1) > 1:
            raise NotImplementedError(
                f"routed_ffn on a mesh with {axis}={mesh.shape[axis]}: "
                "the dropless top-k path replicates its experts and "
                f"sorts each chip's own tokens; {what} is not "
                "implemented for it yet, and the shard_map would gather "
                "every expert onto every chip each step. The top-1 "
                "moe_ffn / SwitchFFN still run under ep.")
    batch_axes = tuple(a for a in (AXIS_DP, AXIS_FSDP)
                       if mesh.shape.get(a, 1) > 1)
    seq_axis = AXIS_SP if mesh.shape.get(AXIS_SP, 1) > 1 else None
    if (batch % math.prod(mesh.shape[a] for a in batch_axes)
            or (seq_axis and seq % mesh.shape[seq_axis])):
        return (), None
    return batch_axes, seq_axis


def routed_ffn(x, router_w, w_gate, w_up, w_down, *, top_k: int,
               norm_topk_prob: bool = False, mesh=None):
    """Dropless top-k mixture of SwiGLU experts.

    x:        [batch, seq, d] (or [tokens, d]) activations
    router_w: [d, E]          bias-free router
    w_gate, w_up: [E, d, f];  w_down: [E, f, d]

    Returns ``(y, aux, z, load)``: the output in ``x``'s shape and
    dtype (sum over each token's ``top_k`` experts of router
    probability times the expert's output; the probabilities are
    renormalised over the ``top_k`` only with ``norm_topk_prob``) and,
    in float32, the load-balancing loss ``E * sum_e f_e P_e`` (the
    published code's form: ``f_e`` sums to ``top_k``), the router
    z-loss ``mean(logsumexp(logits)^2)`` and the routes each expert
    received, ``[E]``, summing to ``tokens * top_k``.

    On a ``mesh`` that shards tokens (dp, fsdp on the batch, sp on the
    sequence) each chip routes, sorts and computes the tokens it holds
    under ``shard_map``, experts replicated; the three returned
    statistics are those of the global batch. A sort over a dimension
    sharded over ``dp`` would gather every token to every chip.
    ``ep > 1`` and ``tp > 1`` raise ``NotImplementedError``.
    """
    local = functools.partial(_routed_ffn_local, top_k=top_k,
                              norm_topk_prob=norm_topk_prob)
    batch_axes, seq_axis = _token_axes(
        mesh, x.shape[0], x.shape[1] if x.ndim == 3 else 1)
    axes = batch_axes + ((seq_axis,) if seq_axis else ())
    tokens = math.prod(x.shape[:-1])
    if axes:
        from jax.sharding import PartitionSpec as P
        held = P(batch_axes or None, seq_axis)
        # All mesh axes manual, as in ops/attention.py and the chunked
        # cross-entropy: the weights enter replicated, so the transpose
        # sums their gradients over the axes once.
        out = jax.shard_map(
            functools.partial(local, over=axes), mesh=mesh,
            in_specs=(held, P(), P(), P(), P()),
            out_specs=(held, P(), P(), P()), check_vma=False)(
                x, router_w, w_gate, w_up, w_down)
        tokens //= math.prod(mesh.shape[a] for a in axes)
    else:
        out = local(x, router_w, w_gate, w_up, w_down)
    tracing.note_trace(
        moe_tokens=tokens, moe_experts=router_w.shape[-1],
        moe_top_k=top_k, moe_routes=tokens * top_k,
        moe_path=grouped_matmul_path(), moe_axes=list(axes))
    return out

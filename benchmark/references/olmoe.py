"""OLMoE as plain ``jax.numpy`` in float32: the configuration's plain
reference. It shares no code with ``ray_tpu/``: it reads the program's
parameter tree and the same batch and computes the published model the
straightforward way (the ``olmoe`` modelling code and arXiv:2409.02060)
— whole score matrices, whole logits, RoPE by ``rotate_half``, an
RMSNorm over the whole q and k projections before the head split, and
the experts the plain way: **every expert on every token**, times the
token's top-k router probability for that expert or zero. So it has no
sort, no groups and no kernel to share a mistake with. The gradient is
``jax.grad``'s.

Beside the loss and the gradient norm it returns what the program's
step reports: the LM loss, the load-balancing loss in the published
code's form (``E * sum_e f_e P_e``; ``f_e`` the routes that went to
``e`` over the number of tokens, summing to ``k`` over the experts,
``P_e`` the mean router probability), the router z-loss (mean of
``logsumexp(logits)^2``) — both averaged over the layers — and the
largest expert's routes over the mean in the worst layer. The two
router losses are those of the whole batch (``f_e`` and ``P_e`` are
means over every token of it), so the batch is walked a row at a time
twice: once for the router's sums, once for the gradient with those
sums as constants of the other rows.

It runs on the chip after the window, beside the live train state
(6.3 GB of 15.75 at the published widths), so it is frugal with
memory and not with time: the experts are walked eight at a time and
the heads one at a time under ``jax.checkpoint``, so a row's ``[seq,
experts, width]`` intermediates and its sixteen score matrices are
never whole together; and the gradient is taken in several passes over
the rows, each pass with respect to a part of the parameters (every
leaf larger than a fifth of them alone, the others together), so only
that part's sum and one row's contribution are alive at a time.

``spec["operand_dtype"]`` (absent in a run of the benchmark) gives the
reading that the configuration's limit is set against from below: the
same computation with every matmul operand that the program holds in
its compute type rounded to that type first (``float8_e4m3fn``: the
precision under the configuration's bfloat16; scaled per tensor so
that its largest element is the type's largest, as fp8 training
recipes do), float32 accumulation, the router's weight left in float32
as the program leaves it. ``tools/olmoe_limit.py`` takes both readings.
"""

from __future__ import annotations

import math

EXPERTS_AT_A_TIME = 8
OWN_PASS_ABOVE = 0.2     # of all parameters: such a leaf gets its own pass


def _same(x):
    return x


def _rounder(dtype):
    """x -> x rounded to ``dtype`` and back, float32; the gradient
    passes straight through (the operand is rounded, the cotangent is
    not). None: x itself."""
    if dtype is None:
        return _same
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    narrow = dtype.itemsize == 1

    def rnd(x):
        scale = (float(jnp.finfo(dtype).max)
                 / jnp.maximum(jnp.abs(x).max(), 1e-30)) if narrow else 1.0
        rounded = (x * scale).astype(dtype).astype(x.dtype) / scale
        return x + jax.lax.stop_gradient(rounded - x)
    return rnd


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate_half(x):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, theta):
    """x: [rows, seq, heads, dim], the Hugging Face way."""
    import jax.numpy as jnp
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


def _attention(q, k, v, rnd=_same):
    """Causal softmax attention, [rows, seq, heads, dim]; a head's
    whole score matrix at a time."""
    import jax
    import jax.numpy as jnp

    t = q.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):
        q, k, v = qkv                               # [rows, seq, dim]
        s = jnp.einsum("btd,bsd->bts", rnd(q), rnd(k)) \
            / math.sqrt(q.shape[-1])
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", rnd(w), rnd(v))

    y = jax.lax.map(head, tuple(jnp.moveaxis(z, 2, 0) for z in (q, k, v)))
    return jnp.moveaxis(y, 0, 2)


def _experts(h, mix, gate, up, down, rnd=_same):
    """sum_e mix[..., e] * down_e(silu(gate_e h) * up_e h): every
    expert on every token, a few experts at a time."""
    import jax
    import jax.numpy as jnp

    e = gate.shape[0]
    g = math.gcd(e, EXPERTS_AT_A_TIME)

    @jax.checkpoint
    def some(group):
        gate, up, down, mix = group
        a = jax.nn.silu(jnp.einsum("btd,edf->ebtf", h, rnd(gate))) \
            * jnp.einsum("btd,edf->ebtf", h, rnd(up))
        return jnp.einsum("ebtf,efd,ebt->btd", rnd(a), rnd(down), mix)

    h = rnd(h)
    mix = jnp.moveaxis(mix, -1, 0)                  # [E, rows, seq]
    return jax.lax.map(some, tuple(
        z.reshape(e // g, g, *z.shape[1:])
        for z in (gate, up, down, mix))).sum(0)


def forward(params, tokens, spec: dict):
    """(final hidden states [rows, seq, d], per layer the router's
    logits [rows, seq, E] and chosen experts [rows, seq, k])."""
    import jax

    eps, heads, k = spec["rms_eps"], spec["n_head"], spec["top_k"]
    rnd = _rounder(spec.get("operand_dtype"))
    rows, t = tokens.shape
    x = params["wte"]["embedding"][tokens]
    routed = []
    for i in range(spec["n_layer"]):
        p = params[f"h_{i}"]
        a, m = p["attn"], p["mlp"]
        h = rnd(_rms_norm(x, p["attn_norm"]["scale"], eps))
        q = _rms_norm(h @ rnd(a["q"]["kernel"]), a["q_norm"]["scale"], eps)
        kk = _rms_norm(h @ rnd(a["k"]["kernel"]), a["k_norm"]["scale"], eps)
        v = h @ rnd(a["v"]["kernel"])
        q, kk, v = (z.reshape(rows, t, heads, -1) for z in (q, kk, v))
        y = _attention(_rope(q, spec["rope_theta"]),
                       _rope(kk, spec["rope_theta"]), v, rnd)
        x = x + rnd(y.reshape(rows, t, -1)) @ rnd(a["proj"]["kernel"])
        h = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        logits = rnd(h) @ m["gate"]["kernel"]
        probs = jax.nn.softmax(logits, axis=-1)
        top, chosen = jax.lax.top_k(probs, k)
        if spec["norm_topk_prob"]:
            top = top / top.sum(-1, keepdims=True)
        # [rows, seq, E]: the token's weight for each expert, or zero
        mix = (jax.nn.one_hot(chosen, probs.shape[-1], dtype=probs.dtype)
               * top[..., None]).sum(-2)
        ex = m["experts"]
        x = x + _experts(h, mix, ex["gate_proj"], ex["up_proj"],
                         ex["down_proj"], rnd)
        routed.append((logits, chosen))
    return rnd(_rms_norm(x, params["norm_f"]["scale"], eps)), routed


def _router_sums(routed):
    """Over the tokens in hand, stacked over the layers: routes per
    expert [L, E], sum of the probabilities [L, E], sum of
    logsumexp(logits)^2 [L]."""
    import jax
    import jax.numpy as jnp
    out = []
    for logits, chosen in routed:
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        out.append((jax.nn.one_hot(chosen, logits.shape[-1]).sum((0, 1, 2)),
                    jax.nn.softmax(logits, -1).sum((0, 1)),
                    (lse ** 2).sum()))
    return [jnp.stack(x) for x in zip(*out)]


def _lm_loss(head, hidden, targets):
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(hidden @ head, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).mean()


def _passes(leaves) -> list[list[int]]:
    """Which leaves each gradient pass differentiates."""
    total = sum(x.size for x in leaves)
    own = [[i] for i, x in enumerate(leaves)
           if x.size > OWN_PASS_ABOVE * total]
    rest = [i for i, x in enumerate(leaves)
            if x.size <= OWN_PASS_ABOVE * total]
    return own + ([rest] if rest else [])


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the six numbers, the gradient tree or None) of the whole batch
    at ``params``, float32 throughout. ``batch`` is {"tokens",
    "targets"}, [rows, seq], walked a row at a time. ``spec``:
    n_layer, n_head, top_k, norm_topk_prob, rms_eps, rope_theta,
    aux_loss_coef, z_loss_coef, and for the low reading operand_dtype. Without ``keep_grads`` a part's
    gradient lives only until its squared norm is taken."""
    import jax
    import jax.numpy as jnp

    rows, seq = batch["tokens"].shape
    n_tokens = rows * seq
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    tokens = batch["tokens"].reshape(rows, 1, seq)
    targets = batch["targets"].reshape(rows, 1, seq)

    @jax.jit
    def router_pass(params, tokens):
        """The router's sums over the whole batch: the two router
        losses are functions of means over every token of it."""
        load, prob_sum, z_sum = (x.sum(0) for x in jax.lax.map(
            lambda tok: _router_sums(forward(params, tok, spec)[1]), tokens))
        f = load / n_tokens                     # sums to k over experts
        e = load.shape[-1]
        return {"f": f,
                "moe_aux_loss": (e * (f * prob_sum / n_tokens).sum(-1)).mean(),
                "moe_z_loss": (z_sum / n_tokens).mean(),
                "moe_load_max_over_mean":
                    (load.max(-1) / load.mean(-1)).max()}

    def row_loss(params, f, tok, tgt):
        """One row's share of the loss. f_e is a count and has no
        gradient; P_e and the z-loss are sums over tokens, so a row's
        part is its own sum over the batch's token count."""
        hidden, routed = forward(params, tok, spec)
        _, p_row, z_row = _router_sums(routed)
        aux_row = (f.shape[-1] * (f * p_row / n_tokens).sum(-1)).mean()
        head = _rounder(spec.get("operand_dtype"))(
            params["lm_head"]["kernel"])
        lm_row = _lm_loss(head, hidden, tgt) / rows
        return (lm_row + spec["aux_loss_coef"] * aux_row
                + spec["z_loss_coef"] * (z_row / n_tokens).mean()), lm_row

    leaves, tree = jax.tree_util.tree_flatten(params)

    @jax.jit(static_argnums=(0,))
    def grad_pass(which, leaves, f, tokens, targets):
        """(LM loss, the summed gradient of the leaves ``which``)."""
        def of_part(part, tok, tgt):
            full = list(leaves)
            for i, x in zip(which, part):
                full[i] = x
            return row_loss(jax.tree_util.tree_unflatten(tree, full),
                            f, tok, tgt)

        part = [leaves[i] for i in which]

        def one(carry, xt):
            (_, lm_row), grads = jax.value_and_grad(
                of_part, has_aux=True)(part, *xt)
            lm, acc = carry
            return (lm + lm_row, [a + g for a, g in zip(acc, grads)]), None

        (lm, acc), _ = jax.lax.scan(
            one, (jnp.zeros((), jnp.float32),
                  [jnp.zeros_like(x) for x in part]), (tokens, targets))
        return lm, (acc if keep_grads
                    else sum(jnp.sum(g ** 2) for g in acc))

    with jax.default_matmul_precision("highest"):
        out = router_pass(params, tokens)
        f = out.pop("f")
        sq, grads = 0.0, [None] * len(leaves)
        for which in _passes(leaves):
            lm, got = grad_pass(tuple(which), leaves, f, tokens, targets)
            if keep_grads:
                for i, g in zip(which, got):
                    grads[i] = g
                got = sum(jnp.sum(g ** 2) for g in got)
            sq += float(got)
    out = {k: float(v) for k, v in out.items()}
    out["lm_loss"] = float(lm)
    out["loss"] = (out["lm_loss"] + spec["aux_loss_coef"] * out["moe_aux_loss"]
                   + spec["z_loss_coef"] * out["moe_z_loss"])
    out["grad_norm"] = math.sqrt(sq)
    return out, (jax.tree_util.tree_unflatten(tree, grads)
                 if keep_grads else None)


def loss_and_grad_norm(params, batch, spec: dict) -> dict:
    """{"loss", "grad_norm", "lm_loss", "moe_aux_loss", "moe_z_loss",
    "moe_load_max_over_mean"}: ``loop.py`` holds every key against the
    metric of that name of the program's first dispatch."""
    return loss_and_grads(params, batch, spec, keep_grads=False)[0]

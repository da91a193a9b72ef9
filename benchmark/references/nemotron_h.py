"""Nemotron-H as plain ``jax.numpy`` in float32: the configuration's
plain reference. It shares no code with ``ray_tpu/``: it reads the
program's parameter tree and the same batch and computes the model the
straightforward way, from the ``nemotron_h`` layer equations
(``configs/nemotron-3-nano-30b-a3b.json`` repeats them):

- a Mamba-2 layer by **the recurrence as written**, a ``lax.scan`` over
  time: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t
  + D x_t``, all heads at once. No chunks, no decay squares, no
  cumulative sums: nothing of ``ops/ssm.py``'s algorithm. The
  convolution is four shifted multiply-adds, the gated norm a mean
  square over each group;
- an expert layer with **every held expert on every token**, times the
  token's router weight for that expert or zero: no sort, no groups,
  no kernel. The router is the sigmoid one: the top-k of ``s + b``,
  weights ``s`` without ``b`` over their sum, times the scale. Given
  the same share of the experts as the program (``spec["experts_held"]``),
  it leaves out what the absent experts would add, as the program does;
- attention as a masked softmax over a head's whole score matrix, each
  key/value head serving its group of query heads; no positions.

It runs on the chip after the window, beside the live train state and
the kept initial parameters (12 of 15.75 GB at the published widths),
so it is frugal with memory and not with time. The gradient is taken
**a layer at a time**: the forward pass keeps each layer's input (88 MB
at 8,192 tokens), then each layer is differentiated alone from the
cotangent of its output, its gradient's squared norm is taken and the
gradient dropped. Inside a layer: the time scan is cut into blocks of
``SCAN_BLOCK`` steps under ``jax.checkpoint`` (a block's entering state
is kept, 2 MB for all heads, and its steps recomputed: a plain scan
would keep 2 MB a step, 16 GB a layer); the experts and the attention
heads are walked one at a time under ``jax.checkpoint``.

``spec["operand_dtype"]`` (absent in a run of the benchmark) gives the
reading that the configuration's limit is set against from below: the
same computation with every matmul operand that the program holds in
its compute type rounded to that type first (``references/olmoe.py``'s
``_rounder``), the router's weight and the scan's ``dt`` and decays left
in float32 as the program leaves them. ``tools/nemotron_limit.py`` takes
both readings.
"""

from __future__ import annotations

import math

SCAN_BLOCK = 256


def _rounder(dtype):
    from benchlib import manifest
    return manifest.load_reference("olmoe")._rounder(dtype)


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _recurrence(x, dt, a, b, c):
    """y_t = S_t . C_t with S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t,
    S_0 = 0. x [rows, T, H, P]; dt [rows, T, H]; a [H]; b, c [rows, T,
    H, N] (each head's group already chosen)."""
    import jax
    import jax.numpy as jnp

    rows, t, h, p = x.shape
    block = math.gcd(t, SCAN_BLOCK)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.einsum("rhpn,rhn->rhp", state, c_t)

    @jax.checkpoint
    def steps(state, some):
        return jax.lax.scan(step, state, some)

    inputs = tuple(
        jnp.moveaxis(z, 1, 0).reshape(t // block, block, *z.shape[:1],
                                      *z.shape[2:])
        for z in (x, dt, b, c))
    _, y = jax.lax.scan(
        steps, jnp.zeros((rows, h, p, b.shape[-1]), jnp.float32), inputs)
    return jnp.moveaxis(y.reshape(t, rows, h, p), 0, 1)


def _mamba(p, h, spec, rnd):
    import jax
    import jax.numpy as jnp

    rows, t, _ = h.shape
    heads, dim = spec["mamba_heads"], spec["mamba_head_dim"]
    groups, n = spec["ssm_groups"], spec["ssm_state"]
    inner = heads * dim
    zxbcdt = rnd(h) @ rnd(p["in_proj"]["kernel"])
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:inner + inner + 2 * groups * n]
    dt = zxbcdt[..., -heads:]
    w, bias = p["conv"]["kernel"], p["conv"]["bias"]
    k = w.shape[0]
    back = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(bias + sum(back[:, j:j + t] * w[j] for j in range(k)))
    x = rnd(xbc[..., :inner]).reshape(rows, t, heads, dim)
    b, c = (jnp.repeat(rnd(z_).reshape(rows, t, groups, n),
                       heads // groups, axis=2)
            for z_ in (xbc[..., inner:inner + groups * n],
                       xbc[..., inner + groups * n:]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = _recurrence(x, dt, -jnp.exp(p["A_log"]), b, c) \
        + p["D"][:, None] * x
    y = y.reshape(rows, t, inner) * jax.nn.silu(z)
    y = y.reshape(rows, t, groups, inner // groups)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + spec["rms_eps"])
    y = y.reshape(rows, t, inner) * p["gate_norm"]["scale"]
    return rnd(y) @ rnd(p["out_proj"]["kernel"])


def _attention(p, h, spec, rnd):
    import jax
    import jax.numpy as jnp

    rows, t, _ = h.shape
    heads, kv, dim = spec["n_head"], spec["n_kv_head"], spec["head_dim"]
    h = rnd(h)
    q = (h @ rnd(p["q"]["kernel"])).reshape(rows, t, heads, dim)
    k, v = (jnp.repeat((h @ rnd(p[name]["kernel"])).reshape(rows, t, kv, dim),
                       heads // kv, axis=2) for name in ("k", "v"))
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):
        q, k, v = qkv                               # [rows, seq, dim]
        s = jnp.einsum("btd,bsd->bts", rnd(q), rnd(k)) / math.sqrt(dim)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", rnd(w), rnd(v))

    y = jax.lax.map(head, tuple(jnp.moveaxis(z, 2, 0) for z in (q, k, v)))
    y = jnp.moveaxis(y, 0, 2).reshape(rows, t, heads * dim)
    return rnd(y) @ rnd(p["proj"]["kernel"])


def _moe(p, h, spec, rnd):
    """(the held experts' part of the routed sum plus the shared
    expert, the routes each of the E experts received [E])."""
    import jax
    import jax.numpy as jnp

    first, held = spec["experts_held"]
    scores = jax.nn.sigmoid(rnd(h) @ p["gate"]["kernel"])
    _, chosen = jax.lax.top_k(
        scores + p["gate"]["e_score_correction_bias"], spec["top_k"])
    top = jnp.take_along_axis(scores, chosen, -1)
    if spec["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    top = top * spec["route_scale"]
    picked = jax.nn.one_hot(chosen, scores.shape[-1], dtype=scores.dtype)
    load = picked.sum((0, 1, 2))
    # [held, rows, seq]: the token's weight for each held expert, or zero
    mix = jnp.moveaxis((picked * top[..., None]).sum(-2), -1, 0)
    mix = mix[first:first + held]
    hr = rnd(h)

    @jax.checkpoint
    def one(expert):
        up, down, weight = expert
        a = jnp.square(jax.nn.relu(hr @ rnd(up)))
        return (rnd(a) @ rnd(down)) * weight[..., None]

    ex = p["experts"]
    y = jax.lax.map(one, (ex["up_proj"], ex["down_proj"], mix)).sum(0)
    sh = p["shared"]
    a = jnp.square(jax.nn.relu(hr @ rnd(sh["up"]["kernel"])))
    return y + rnd(a) @ rnd(sh["down"]["kernel"]), load


def _layer(kind: str, spec: dict):
    """(p, x) -> (x + mixer(norm(x)), the routes per expert or None)."""
    rnd = _rounder(spec.get("operand_dtype"))

    def layer(p, x):
        h = _rms_norm(x, p["norm"]["scale"], spec["rms_eps"])
        if kind == "M":
            return x + _mamba(p["mamba"], h, spec, rnd), None
        if kind == "*":
            return x + _attention(p["attn"], h, spec, rnd), None
        y, load = _moe(p["mlp"], h, spec, rnd)
        return x + y, load
    return layer


def _tail(spec: dict):
    """(the final norm and the head, x, targets) -> the LM loss."""
    rnd = _rounder(spec.get("operand_dtype"))

    def tail(p, x, targets):
        import jax
        import jax.numpy as jnp
        h = rnd(_rms_norm(x, p["norm_f"]["scale"], spec["rms_eps"]))
        logp = jax.nn.log_softmax(h @ rnd(p["lm_head"]["kernel"]), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], -1).mean()
    return tail


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the numbers, the gradient tree or None) of the whole batch at
    ``params``, float32 throughout. ``batch`` is {"tokens", "targets"},
    [rows, seq]. ``spec``: pattern, mamba_heads, mamba_head_dim,
    ssm_state, ssm_groups, n_head, n_kv_head, head_dim, top_k,
    norm_topk_prob, route_scale, experts_held (first, count), rms_eps,
    and for the low reading operand_dtype. Without ``keep_grads`` a
    layer's gradient lives only until its squared norm is taken."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    tokens, targets = batch["tokens"], batch["targets"]
    pattern = spec["pattern"]
    forward = {k: jax.jit(_layer(k, spec)) for k in set(pattern)}

    def pull(kind):
        @jax.jit
        def back(p, x, dy):
            return jax.vjp(lambda p, x: _layer(kind, spec)(p, x)[0],
                           p, x)[1](dy)
        return back
    backward = {k: pull(k) for k in set(pattern)}

    def sq(tree):
        return sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(tree))

    grads, total = {}, 0.0

    def took(name, g):
        nonlocal total
        total += float(sq(g))
        if keep_grads:
            grads[name] = g

    with jax.default_matmul_precision("highest"):
        x = params["wte"]["embedding"][tokens]
        inputs, loads = [], []
        for i, kind in enumerate(pattern):
            inputs.append(x)
            x, load = forward[kind](params[f"h_{i}"], x)
            if load is not None:
                loads.append(load)
        tail = {k: params[k] for k in ("norm_f", "lm_head")}
        loss, (g, dx) = jax.jit(jax.value_and_grad(
            _tail(spec), argnums=(0, 1)))(tail, x, targets)
        for name in tail:
            took(name, g[name])
        for i in reversed(range(len(pattern))):
            g, dx = backward[pattern[i]](params[f"h_{i}"], inputs.pop(), dx)
            took(f"h_{i}", g)
        took("wte", {"embedding": jnp.zeros_like(
            params["wte"]["embedding"]).at[tokens].add(dx)})
    out = {"loss": float(loss), "lm_loss": float(loss),
           "grad_norm": math.sqrt(total)}
    if loads:
        first, held = spec["experts_held"]
        load = jnp.stack(loads)
        out["moe_absent_route_share"] = 1.0 - float(
            load[:, first:first + held].sum() / load.sum())
    return out, (grads if keep_grads else None)


def loss_and_grad_norm(params, batch, spec: dict) -> dict:
    """{"loss", "lm_loss", "grad_norm", "moe_absent_route_share"}:
    ``loop.py`` holds every key against the metric of that name of the
    program's first dispatch, all at the configuration's one ``rtol``.
    So the routing statistic is the share of routes that land on
    **absent** experts (0.934 here): bf16 activations flip some 6th
    against 7th choices, which moves the held share (0.066) by up to
    0.36% of itself over nine seeds and this one by 0.026%; the limit
    that the float8 reading has to fail is 0.098%. The load's max over
    mean is left out for the same reason: at 384 routes an expert one
    flipped route moves it 0.26% (PERF.md 6, PR 32)."""
    return loss_and_grads(params, batch, spec, keep_grads=False)[0]

"""ZAYA1 (CCA, a top-1 MLP router with a state that runs from layer to
layer, scaled residuals, a tied table) as plain ``jax.numpy`` in
float32: the configuration's plain reference. It shares no code with
``ray_tpu/``: it reads the program's parameter tree and the same batch
and computes the model the straightforward way, from the layer
equations (``configs/zaya1-8b.json`` repeats them):

- CCA with its **convolutions as shifted sums** (``y_t = sum_j w_j
  x_{t-(K-1-j)} + b``, the second one a head's 128 channels mixing
  among themselves), the q-k mean, the L2 norm with its temperature,
  the rotation of the first lanes in halves, and attention as a
  **masked softmax over each head's score rows**, a block of rows at a
  time so that 8,192 rows fit; a query head reads key/value head ``i //
  (H / G)``, value head 1 being the previous token's projection;
- the router **threaded by hand**: ``r_l = h W_d + b_d + gamma_l
  r_{l-1}``, the three-layer GeLU MLP, softmax, the arg-max of ``p +
  b``, the weight ``p`` without ``b``; the routed layer with **every
  held expert on every token**, times the token's weight for that
  expert or zero: no sort, no groups. Given the same share of the
  experts as the program (``spec["experts_held"]``), it leaves out what
  the absent experts would add, as the program does;
- ``x <- (a_r x + b_r) + (a_o f(RMSNorm(x)) + b_o)`` around both
  sublayers; the head is the embedding table.

One departure in how the weights are laid out, not in what is
computed: the program holds ``W_q | W_k`` as one array (``attn/qk``)
and ``W_v1 | W_v2`` as another (``attn/v``), and shifts the previous
token's half after its projection (``shift(h) W = shift(h W)``).

It runs on the chip after the window, beside the live train state and
the kept initial parameters, so it is frugal with memory and not with
time, as ``references/joyai.py``: the gradient is taken **a layer at a
time** (each block is differentiated alone from the cotangents of its
two outputs, its gradient's squared norm taken and the gradient
dropped), the heads, the row blocks, the experts and the loss's row
chunks are walked one at a time under ``jax.checkpoint``. The table's
gradient, which two paths reach, is summed before its norm is taken.

``spec["adamw"]`` adds the optimizer's first step (``references/
joyai.py::adamw_first_change``, the same rule) and ``update_norm``;
``spec["operand_dtype"]`` (absent in a run of the benchmark) gives the
reading that the configuration's limit is set against from below: every
matmul operand that the program holds in its compute type rounded to
that type first (``references/olmoe.py``'s ``_rounder``), the router's
matmuls left in float32 as the program leaves them.
"""

from __future__ import annotations

import math

ROW_BLOCK = 2048     # score rows and loss rows computed at a time


def _other(name: str):
    from benchlib import manifest
    return manifest.load_reference(name)


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _shift(x, by: int = 1):
    """Row ``t - by`` at row ``t`` of [rows, seq, ...]; zeros first."""
    import jax.numpy as jnp
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :by]), x[:, :-by]], axis=1)


def _rope_half(x, rotary: int, theta: float):
    """Rotate lane i with lane i + rotary/2, i < rotary/2, of the last
    axis of x [rows, seq, heads, D] by position x theta^(-2i / rotary);
    the lanes from ``rotary`` on stay."""
    import jax.numpy as jnp
    t, half = x.shape[1], rotary // 2
    inv = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv)[None, :, None]
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang),
                            x[..., rotary:]], axis=-1)


def _softmax_attention(q, k, v, rnd):
    """q [rows, seq, H, D] against k, v [rows, seq, G, D], causal, head
    i on group i // (H / G); a head and a block of score rows at a
    time."""
    import jax
    import jax.numpy as jnp

    rows, t, heads, d = q.shape
    rep = heads // k.shape[2]
    blk = min(t, ROW_BLOCK)
    at = jnp.arange(t)

    @jax.checkpoint
    def block(qb, kh, vh, start):
        s = jnp.einsum("btd,bsd->bts", rnd(qb), rnd(kh)) / math.sqrt(d)
        seen = (start + jnp.arange(blk))[:, None] >= at[None, :]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", rnd(w), rnd(vh))

    def head(qkv):
        qh, kh, vh = qkv                            # [rows, seq, D]
        qb = jnp.moveaxis(qh.reshape(rows, t // blk, blk, d), 1, 0)
        out = jax.lax.map(lambda a: block(a[0], kh, vh, a[1]),
                          (qb, jnp.arange(t // blk) * blk))
        return jnp.moveaxis(out, 0, 1).reshape(rows, t, d)

    per_head = [jnp.moveaxis(q, 2, 0)] + [
        jnp.moveaxis(jnp.repeat(z, rep, axis=2), 2, 0) for z in (k, v)]
    return jnp.moveaxis(jax.lax.map(head, tuple(per_head)), 0, 2)


def _cca(p, h, spec, rnd):
    import jax.numpy as jnp

    rows, t, _ = h.shape
    heads, groups, d = spec["n_head"], spec["n_kv_head"], spec["head_dim"]
    rep = heads // groups
    h = rnd(h)
    qk = h @ rnd(p["qk"]["kernel"])
    both = h @ rnd(p["v"]["kernel"])
    v = jnp.stack([both[..., :d], _shift(both[..., d:])], axis=2)
    # the depthwise convolution, then the one within each head
    w0, w1 = p["conv0"]["kernel"], p["conv1"]["kernel"]
    c = sum(w0[j] * _shift(qk, len(w0) - 1 - j) for j in range(len(w0)))
    c = (c + p["conv0"]["bias"]).reshape(rows, t, heads + groups, d)
    c = sum(jnp.einsum("btgc,gcd->btgd", rnd(_shift(c, len(w1) - 1 - j)),
                       rnd(w1[j])) for j in range(len(w1)))
    c = c + p["conv1"]["bias"].reshape(heads + groups, d)
    q0 = qk[..., :heads * d].reshape(rows, t, groups, rep, d)
    k0 = qk[..., heads * d:].reshape(rows, t, groups, d)
    q = c[:, :, :heads] + ((q0 + k0[:, :, :, None]) / 2).reshape(
        rows, t, heads, d)
    k = c[:, :, heads:] + (q0.mean(3) + k0) / 2

    def unit(z):
        return math.sqrt(d) * z / jnp.sqrt(
            (z * z).sum(-1, keepdims=True) + spec["l2_eps"])
    q = unit(q)
    k = unit(k) * p["temperature"][:, None]
    q, k = (_rope_half(z, spec["rotary_dim"], spec["rope_theta"])
            for z in (q, k))
    o = _softmax_attention(q, k, v, rnd).reshape(rows, t, heads * d)
    return rnd(o) @ rnd(p["out"]["kernel"])


def _route(p, h, state):
    """(weights [rows, seq], experts [rows, seq], this layer's state):
    float32 throughout."""
    import jax
    import jax.numpy as jnp

    def lin(name, z):
        return z @ p[name]["kernel"] + p[name]["bias"]
    state = lin("down", h) + p["gamma"] * state
    z = lin("fc3", jax.nn.gelu(lin("fc2", jax.nn.gelu(
        lin("fc1", state), approximate=False)), approximate=False))
    probs = jax.nn.softmax(z, axis=-1)
    chosen = jnp.argmax(probs + p["balance_bias"], axis=-1)
    return (jnp.take_along_axis(probs, chosen[..., None], -1)[..., 0],
            chosen, state)


def _moe(p, h, state, spec, rnd):
    """(the held experts' part of the routed sum, this layer's router
    state, the routes each of the E experts received [E])."""
    import jax
    import jax.numpy as jnp

    first, held = spec["experts_held"]
    hr = rnd(h)
    weight, chosen, state = _route(p["router"], hr, state)
    load = jax.nn.one_hot(chosen, spec["num_experts"]).sum((0, 1))
    # [held, rows, seq]: the token's weight for each held expert, or zero
    mix = jnp.where(chosen[None] == first + jnp.arange(held)[:, None, None],
                    weight[None], 0.0)

    @jax.checkpoint
    def one(expert):
        gate, up, down, w = expert
        a = jax.nn.silu(hr @ rnd(gate)) * (hr @ rnd(up))
        return (rnd(a) @ rnd(down)) * w[..., None]

    ex = p["experts"]
    y = jax.lax.map(one, (ex["gate_proj"], ex["up_proj"], ex["down_proj"],
                          mix)).sum(0)
    return y, state, load


def _block(spec: dict):
    """(p, x, the previous layer's router state) -> (x, this layer's
    state, the routes per expert)."""
    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))
    eps = spec["rms_eps"]

    def scaled(p, x, y):
        return ((p["stream_scale"] * x + p["stream_bias"])
                + (p["out_scale"] * y + p["out_bias"]))

    def block(p, x, state):
        x = scaled(p["attn_res"], x, _cca(
            p["attn"], _rms_norm(x, p["attn_norm"]["scale"], eps), spec, rnd))
        y, state, load = _moe(
            p["mlp"], _rms_norm(x, p["mlp_norm"]["scale"], eps), state,
            spec, rnd)
        return scaled(p["mlp_res"], x, y), state, load
    return block


def _tail(spec: dict):
    """(the final norm's scale, the table, x, targets) -> the mean
    cross-entropy against the tied head, a chunk of rows at a time."""
    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))

    def tail(scale, table, x, targets):
        import jax
        import jax.numpy as jnp
        h = rnd(_rms_norm(x, scale, spec["rms_eps"]))
        h, tg = h.reshape(-1, h.shape[-1]), targets.reshape(-1)
        n = h.shape[0]
        rows = min(n, ROW_BLOCK)
        head = rnd(table).T

        @jax.checkpoint
        def chunk(part):
            hc, tc = part
            logp = jax.nn.log_softmax(hc @ head, axis=-1)
            return -jnp.take_along_axis(logp, tc[:, None], -1).sum()

        return jax.lax.map(chunk, (h.reshape(n // rows, rows, -1),
                                   tg.reshape(n // rows, rows))).sum() / n
    return tail


def _first_state(tokens, params):
    import jax.numpy as jnp
    width = params["h_0"]["mlp"]["router"]["gamma"].shape[0]
    return jnp.zeros((*tokens.shape, width), jnp.float32)


def forward(params, tokens, spec: dict):
    """(logits [rows, seq, vocab], every layer's router state [L, rows,
    seq, r], the routes per expert [L, E]): the whole forward pass in
    one piece, for tests at small sizes."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        table = params["wte"]["embedding"]
        x, state = table[tokens], _first_state(tokens, params)
        states, loads = [], []
        for i in range(spec["n_layer"]):
            x, state, load = _block(spec)(params[f"h_{i}"], x, state)
            states.append(state)
            loads.append(load)
        logits = _rms_norm(x, params["norm_f"]["scale"],
                           spec["rms_eps"]) @ table.T
    return logits, jnp.stack(states), jnp.stack(loads)


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the numbers, the gradient tree or None) of the whole batch at
    ``params``, float32 throughout. ``batch`` is {"tokens", "targets"},
    [rows, seq]. ``spec``: n_layer, n_head, n_kv_head, head_dim,
    rotary_dim, rope_theta, l2_eps, rms_eps, num_experts, experts_held
    (first, count), and for the low reading operand_dtype. Without
    ``keep_grads`` a block's gradient lives only until its squared norm
    is taken; the kept tree is numpy's, on the host."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    tokens, targets = batch["tokens"], batch["targets"]
    block = _block(spec)
    forward_ = jax.jit(block)

    @jax.jit
    def backward(p, x, state, dx, dstate):
        return jax.vjp(lambda *a: block(*a)[:2], p, x, state)[1](
            (dx, dstate))

    grads, total = {}, 0.0

    def took(name, g):
        nonlocal total
        total += float(sum(jnp.sum(z * z)
                           for z in jax.tree_util.tree_leaves(g)))
        if keep_grads:
            grads[name] = jax.device_get(g)

    with jax.default_matmul_precision("highest"):
        table = params["wte"]["embedding"]
        x, state = table[tokens], _first_state(tokens, params)
        inputs, loads = [], []
        for i in range(spec["n_layer"]):
            inputs.append((x, state))
            x, state, load = forward_(params[f"h_{i}"], x, state)
            loads.append(load)
        loss, (g_norm, g_table, dx) = jax.jit(jax.value_and_grad(
            _tail(spec), argnums=(0, 1, 2)))(
                params["norm_f"]["scale"], table, x, targets)
        took("norm_f", {"scale": g_norm})
        dstate = jnp.zeros_like(state)      # nothing reads the last one
        for i in reversed(range(spec["n_layer"])):
            g, dx, dstate = backward(params[f"h_{i}"], *inputs.pop(),
                                     dx, dstate)
            took(f"h_{i}", g)
        took("wte", {"embedding": g_table.at[tokens].add(dx)})
    first, held = spec["experts_held"]
    load = jnp.stack(loads)
    out = {"loss": float(loss), "grad_norm": math.sqrt(total),
           "moe_absent_route_share": 1.0 - float(
               load[:, first:first + held].sum() / load.sum())}
    return out, (grads if keep_grads else None)


def loss_and_grad_norm(params, batch, spec: dict) -> dict:
    """{"loss", "grad_norm", "moe_absent_route_share"} and, given
    ``spec["adamw"]``, ``"update_norm"``: ``loop.py`` holds every key
    against the metric of that name of the program's first dispatch,
    all at the configuration's one ``rtol``. The routing statistic is
    the share of routes that land on **absent** experts (a half at an
    even load with 8 of 16 held)."""
    adamw = spec.get("adamw")
    out, grads = loss_and_grads(params, batch, spec, keep_grads=bool(adamw))
    if adamw:
        out["update_norm"] = _other("joyai").adamw_first_change(
            params, grads, out["grad_norm"], adamw)
    return out

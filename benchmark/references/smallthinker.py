"""SmallThinker (window and global attention layers side by side, a
router that reads the block's input before attention, ReGLU experts, an
untied head) as plain ``jax.numpy`` in float32: the configuration's
plain reference. It shares no code with ``ray_tpu/``: it reads the
program's parameter tree and the same batch and computes the model the
straightforward way, from the layer equations
(``configs/smallthinker-21b-a3b.json`` repeats them):

- ``h = RMSNorm_in(x)``; **the routes from h**: ``l = h W_r`` over all
  64 experts, the six largest, their weights a softmax over those six
  logits; ``x' = x + Attn(h)``; ``x'' = x' + Experts(RMSNorm_post(x'),
  routes)``;
- attention as a **masked softmax over each head's score rows**, the
  mask written from the definition, ``t - window < j <= t`` in a
  windowed layer and ``j <= t`` in a global one, a head and a block of
  query rows at a time so that ``[rows, 16384]`` fits; q and k rotated
  in halves (lane i with lane i + 64) in the layers whose entry of
  ``rope_period`` is 1, and in no other; a query head reads key/value
  head ``i // (H / G)``;
- the routed layer with **every held expert on every token**, times the
  token's weight for that expert or zero: no sort, no groups; an expert
  is ``down(relu(gate x) * up x)``. Given the same share of the experts
  as the program (``spec["experts_held"]``), it leaves out what the
  absent experts would add, as the program does;
- a final RMSNorm and the untied head, the loss a chunk of rows at a
  time.

It runs on the chip after the window, beside the live train state and
the kept initial parameters, so it is frugal with memory and not with
time, as ``references/zaya.py``: the gradient is taken **a layer at a
time** (each block is differentiated alone from its output's
cotangent, its gradient's squared norm taken and the gradient dropped
unless the optimizer's step is asked for), the heads, the row blocks,
the experts and the loss's row chunks are walked one at a time under
``jax.checkpoint``.

``spec["adamw"]`` adds the optimizer's first step (``references/
joyai.py::adamw_first_change``, the same rule) and ``update_norm``;
``spec["operand_dtype"]`` (absent in a run of the benchmark) gives the
reading that the configuration's limit is set against from below: every
matmul operand that the program holds in its compute type rounded to
that type first (``references/olmoe.py``'s ``_rounder``), the router's
matmul left in float32 as the program leaves it.
"""

from __future__ import annotations

import math
import re

ROW_BLOCK = 2048     # score rows and loss rows computed at a time


def _other(name: str):
    from benchlib import manifest
    return manifest.load_reference(name)


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope_half(x, theta: float):
    """Rotate lane i with lane i + D/2 of the last axis of x [rows,
    seq, heads, D] by position x theta^(-2i / D)."""
    import jax.numpy as jnp
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv)[None, :, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def seen(rows, cols, window):
    """The mask from the definition: query row t sees key j where ``j <=
    t`` and, under a window, ``t - window < j``."""
    ok = cols[None, :] <= rows[:, None]
    if window is not None:
        ok &= cols[None, :] > rows[:, None] - window
    return ok


def _softmax_attention(q, k, v, window, rnd):
    """q [rows, seq, H, D] against k, v [rows, seq, G, D], head i on
    group i // (H / G); a head and a block of score rows at a time."""
    import jax
    import jax.numpy as jnp

    rows, t, heads, d = q.shape
    rep = heads // k.shape[2]
    blk = min(t, ROW_BLOCK)
    at = jnp.arange(t)

    @jax.checkpoint
    def block(qb, kh, vh, start):
        s = jnp.einsum("btd,bsd->bts", rnd(qb), rnd(kh)) / math.sqrt(d)
        ok = seen(start + jnp.arange(blk), at, window)
        w = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
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


def _attention(p, h, layer: int, spec, rnd):
    rows, t, _ = h.shape
    heads, groups, d = spec["n_head"], spec["n_kv_head"], spec["head_dim"]
    h = rnd(h)
    q = (h @ rnd(p["q"]["kernel"])).reshape(rows, t, heads, d)
    k = (h @ rnd(p["k"]["kernel"])).reshape(rows, t, groups, d)
    v = (h @ rnd(p["v"]["kernel"])).reshape(rows, t, groups, d)
    if spec["rope_period"][layer % len(spec["rope_period"])]:
        q, k = (_rope_half(z, spec["rope_theta"]) for z in (q, k))
    windowed = spec["window_period"][layer % len(spec["window_period"])]
    o = _softmax_attention(q, k, v, spec["window"] if windowed else None,
                           rnd)
    # the mean square of the rows that see a whole window (a windowed
    # layer's; 0 for a global one): ``attn_window_out_rms`` is made of it
    whole = o[:, min(spec["window"], t) - 1:]
    return (rnd(o.reshape(rows, t, heads * d)) @ rnd(p["out"]["kernel"]),
            (whole * whole).mean() if windowed else 0.0)


def routes(router_w, h, spec):
    """(weights [rows, seq, k], experts [rows, seq, k]) from the block's
    normed input, float32: the ``top_k`` largest logits, a softmax over
    those alone."""
    import jax
    logits = h @ router_w
    top, chosen = jax.lax.top_k(logits, spec["top_k"])
    return jax.nn.softmax(top, axis=-1), chosen


def experts_part(p, h, weights, chosen, held, rnd):
    """The part of the routed sum that experts ``held = (first, count)``
    give, ``p`` holding their matrices: every one of them on every
    token, times the token's weight for it or zero."""
    import jax
    import jax.numpy as jnp

    first, count = held
    hr = rnd(h)
    # [count, rows, seq]: the token's weight for each held expert, or zero
    mix = jnp.where(
        chosen[None] == (first + jnp.arange(count))[:, None, None, None],
        weights[None], 0.0).sum(-1)

    @jax.checkpoint
    def one(expert):
        gate, up, down, w = expert
        a = jax.nn.relu(hr @ rnd(gate)) * (hr @ rnd(up))
        return (rnd(a) @ rnd(down)) * w[..., None]

    return jax.lax.map(one, (p["gate_proj"], p["up_proj"], p["down_proj"],
                             mix)).sum(0)


def _block(spec: dict, layer: int):
    """(p, x) -> (x, the routes each of the E experts received [E],
    the routes' weights and experts [rows, seq, k], the mean square of
    the windowed core's output over the rows that see a whole window)."""
    import jax

    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))
    eps = spec["rms_eps"]

    def block(p, x):
        h = _rms_norm(x, p["attn_norm"]["scale"], eps)
        weights, chosen = routes(p["router"]["kernel"], rnd(h), spec)
        load = jax.nn.one_hot(chosen, spec["num_experts"]).sum((0, 1, 2))
        attended, out_sq = _attention(p["attn"], h, layer, spec, rnd)
        x = x + attended
        y = experts_part(p["mlp"], _rms_norm(x, p["mlp_norm"]["scale"], eps),
                         weights, chosen, spec["experts_held"], rnd)
        return x + y, load, weights, chosen, out_sq
    return block


def _tail(spec: dict):
    """(the final norm's scale, the head [d, V], x, targets) -> the mean
    cross-entropy, a chunk of rows at a time."""
    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))

    def tail(scale, head, x, targets):
        import jax
        import jax.numpy as jnp
        h = rnd(_rms_norm(x, scale, spec["rms_eps"]))
        h, tg = h.reshape(-1, h.shape[-1]), targets.reshape(-1)
        n = h.shape[0]
        rows = min(n, ROW_BLOCK)
        head = rnd(head)

        @jax.checkpoint
        def chunk(part):
            hc, tc = part
            logp = jax.nn.log_softmax(hc @ head, axis=-1)
            return -jnp.take_along_axis(logp, tc[:, None], -1).sum()

        return jax.lax.map(chunk, (h.reshape(n // rows, rows, -1),
                                   tg.reshape(n // rows, rows))).sum() / n
    return tail


def forward(params, tokens, spec: dict):
    """(logits [rows, seq, vocab], every layer's routes ([L, rows, seq,
    k] weights, [L, rows, seq, k] experts), the routes per expert [L,
    E]): the whole forward pass in one piece, for tests at small
    sizes."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = params["wte"]["embedding"][tokens]
        made, loads = [], []
        for i in range(spec["n_layer"]):
            x, load, *route, _ = _block(spec, i)(params[f"h_{i}"], x)
            made.append(route)
            loads.append(load)
        logits = _rms_norm(x, params["norm_f"]["scale"],
                           spec["rms_eps"]) @ params["lm_head"]["kernel"]
    return (logits, (jnp.stack([w for w, _ in made]),
                     jnp.stack([e for _, e in made])), jnp.stack(loads))


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the numbers, the gradient tree or None, the routes each expert
    of each layer received [L, E]) of the whole batch at ``params``,
    float32 throughout. ``batch`` is {"tokens", "targets"}, [rows,
    seq]. ``spec``: n_layer, n_head, n_kv_head, head_dim, window,
    window_period, rope_period, rope_theta, rms_eps, num_experts, top_k,
    experts_held (first, count), for the low reading operand_dtype,
    and ``grad_groups`` {name: regular expression over a gradient
    leaf's path, ``h_1/attn/q/kernel``}: the norm of the leaves each
    finds is among the numbers under its name.
    Without ``keep_grads`` a block's gradient lives only until its
    squared norm is taken; the kept tree is numpy's, on the host."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    tokens, targets = batch["tokens"], batch["targets"]
    blocks = [_block(spec, i) for i in range(spec["n_layer"])]
    # a windowed layer and a global one are two programs; layers of one
    # kind share theirs
    kinds = {}

    def programs(i):
        key = (spec["window_period"][i % len(spec["window_period"])],
               spec["rope_period"][i % len(spec["rope_period"])])
        if key not in kinds:
            block = blocks[i]
            kinds[key] = (jax.jit(block), jax.jit(
                lambda p, x, dx: jax.vjp(lambda *a: block(*a)[0], p, x)[1](
                    dx)))
        return kinds[key]

    grads, squares = {}, {}     # squares: a leaf's path -> its squared norm

    def took(name, g):
        for path, z in jax.tree_util.tree_flatten_with_path(g)[0]:
            squares["/".join([name, *(k.key for k in path)])] = float(
                jnp.sum(z * z))
        if keep_grads:
            grads[name] = jax.device_get(g)

    with jax.default_matmul_precision("highest"):
        table = params["wte"]["embedding"]
        x = table[tokens]
        inputs, loads, out_sq = [], [], []
        for i in range(spec["n_layer"]):
            inputs.append(x)
            x, load, _, _, sq = programs(i)[0](params[f"h_{i}"], x)
            loads.append(load)
            if spec["window_period"][i % len(spec["window_period"])]:
                out_sq.append(float(sq))
        loss, (g_norm, g_head, dx) = jax.jit(jax.value_and_grad(
            _tail(spec), argnums=(0, 1, 2)))(
                params["norm_f"]["scale"], params["lm_head"]["kernel"], x,
                targets)
        took("norm_f", {"scale": g_norm})
        took("lm_head", {"kernel": g_head})
        for i in reversed(range(spec["n_layer"])):
            g, dx = programs(i)[1](params[f"h_{i}"], inputs.pop(), dx)
            took(f"h_{i}", g)
        took("wte", {"embedding": jnp.zeros_like(table).at[tokens].add(dx)})
    first, held = spec["experts_held"]
    load = jnp.stack(loads)
    out = {"loss": float(loss),
           "grad_norm": math.sqrt(sum(squares.values())),
           "moe_absent_route_share": 1.0 - float(
               load[:, first:first + held].sum() / load.sum()),
           "attn_window_out_rms": math.sqrt(sum(out_sq) / len(out_sq))}
    for name, pattern in spec.get("grad_groups", {}).items():
        out[name] = math.sqrt(sum(
            sq for path, sq in squares.items() if re.search(pattern, path)))
    return out, (grads if keep_grads else None), load


def loss_and_grad_norm(params, batch, spec: dict, load=None) -> dict:
    """{"loss", "grad_norm", "moe_absent_route_share",
    "attn_window_out_rms"}, a key a group of ``spec["grad_groups"]``
    and, given ``spec["adamw"]``, ``"update_norm"``: ``loop.py`` holds every key against the metric
    of that name of the program's first dispatch, all at the
    configuration's one ``rtol``. The routing statistic is the share of
    routes that land on **absent** experts (three quarters at an even
    load with 16 of 64 held); ``attn_window_out_rms`` is the root mean
    square of the windowed cores' output over the rows that see a whole
    window. A list given as ``load`` receives a row a layer of the
    routes each expert drew."""
    adamw = spec.get("adamw")
    out, grads, routes = loss_and_grads(params, batch, spec,
                                        keep_grads=bool(adamw))
    if load is not None:
        load.extend(routes.tolist())
    if adamw:
        out["update_norm"] = _other("joyai").adamw_first_change(
            params, grads, out["grad_norm"], adamw)
    return out

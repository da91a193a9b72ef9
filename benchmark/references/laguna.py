"""Laguna-XS.2 (full and sliding attention layers of different head
counts side by side, YaRN on half of a full layer's lanes, a gate a
head, a leading dense MLP, sigmoid-routed SwiGLU experts beside a shared
one, an untied head) as plain ``jax.numpy`` in float32: the
configuration's plain reference. It shares no code with ``ray_tpu/``:
it reads the program's parameter tree and the same batch and computes
the model the straightforward way, from the layer equations
(``configs/laguna-xs.2.json`` repeats them):

- ``x' = x + Attn_l(RMSNorm(x))``; ``x'' = x' + MLP_l(RMSNorm(x'))``;
- a layer's **head count is read off its own ``W_q``** (columns / 128)
  and its kind from ``spec["layer_types"]``, its entry of the published
  list; a query head reads key/value head ``i // (H_l / 8)``;
- attention as a **masked softmax over each head's score rows**, the
  mask written from the definition (``references/smallthinker.py::seen``:
  ``t - window < j <= t`` in a sliding layer, ``j <= t`` in a full one),
  a head and a block of query rows at a time so that ``[rows, 16384]``
  fits, a group's heads in turn against the group's one key and value
  (no copies);
- **positions from the equations**: a sliding layer rotates every lane
  in halves at ``theta_s^(-2i/128)``; a full layer rotates the first
  ``d`` = 64 lanes in halves by YaRN's frequencies, ``f_i =
  theta^(-2i/d)``, ``c(n) = d ln(L / (2 pi n)) / (2 ln theta)``, ``low
  = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))`` clamped to
  ``[0, d - 1]``, ``r_i = clip((i - low) / (high - low), 0, 1)``,
  ``inv_i = (f_i / factor) r_i + f_i (1 - r_i)``, cos and sin times the
  attention factor, and leaves lanes 64-127 as they are;
- **the gate**: ``g = sigmoid(h W_g)``, a value a head and a token,
  times the head's output before ``W_o``;
- the routed layer with **every held expert on every token** times its
  route's weight or zero, one expert at a time into one sum (32 experts'
  outputs side by side would be 4.3 GB at 16,384 rows); the float32
  sigmoid router, the top-8 of ``s + b``, weights ``s_i / (sum s_i +
  1e-20) x 2.5``; the shared expert and layer 0's MLP plain SwiGLU
  (``references/joyai.py::_swiglu``); the loss a chunk of rows at a time
  (``references/smallthinker.py::_tail``).
  Given the same share of the experts as the program
  (``spec["experts_held"]``), it leaves out what the absent experts would
  add, as the program does.

It runs on the chip after the window, beside the live train state, so
it is frugal with memory and not with time: the gradient is taken **half
a block at a time** (attention, then the MLP, each differentiated alone
from its output's cotangent: a whole block's backward asked for 7.4 GB
beside 6.9 GB of train state), and the parameters may wait on the host
(numpy): a half's are on the device only while it runs.

``spec["adamw"]`` adds the optimizer's first step
(``references/joyai.py::adamw_first_change``) and ``update_norm``;
``spec["operand_dtype"]`` (absent in a run of the benchmark) gives the
reading that the configuration's limit is set against from below: every
matmul operand that the program holds in its compute type rounded to
that type first (``references/olmoe.py``'s ``_rounder``), the router's
matmul left in float32 as the program leaves it.
"""

from __future__ import annotations

import math

SLIDING = "sliding_attention"
ROW_BLOCK = 2048     # score rows computed at a time


def _other(name: str):
    from benchlib import manifest
    return manifest.load_reference(name)


def yarn_inv_freq(d: int, theta: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float):
    """(``inv_i`` for the ``d / 2`` pairs of ``d`` rotated lanes, numpy
    float64; ``low``; ``high``), from the equations in this file's
    head."""
    import numpy as np

    def c(n):
        return d * math.log(original_len / (2 * math.pi * n)) / (
            2 * math.log(theta))
    low = max(math.floor(c(beta_fast)), 0)
    high = min(math.ceil(c(beta_slow)), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)
    r = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    return f / factor * r + f * (1.0 - r), low, high


def positions(spec: dict, sliding: bool):
    """(``inv_i`` of the rotated pairs, the factor on cos and sin) of a
    layer's kind."""
    import numpy as np
    if sliding:
        d = spec["head_dim"]
        return spec["sliding_theta"] ** (
            -2.0 * np.arange(d // 2, dtype=np.float64) / d), 1.0
    y = spec["yarn"]
    inv, _, _ = yarn_inv_freq(
        spec["rotated_lanes"], spec["full_theta"], y["factor"],
        y["original_len"], y["beta_fast"], y["beta_slow"])
    # the published number is 0.1 ln(factor) + 1, YaRN's own default
    return inv, y["attention_factor"] or 0.1 * math.log(y["factor"]) + 1.0


def _rotate(x, inv, amplitude: float):
    """The first ``2 len(inv)`` lanes of x [rows, seq, heads, D] rotated
    in halves (lane i with lane i + len(inv)) by position x ``inv_i``,
    cos and sin times ``amplitude``; the other lanes as they are."""
    import jax.numpy as jnp
    t, half = x.shape[1], len(inv)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32))[None, :, None]
    cos, sin = jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def _softmax_attention(q, k, v, window, rnd):
    """q [rows, seq, H, D] against k, v [rows, seq, G, D], head i on
    group i // (H / G): a masked softmax a head and a block of score
    rows at a time, the mask from the definition
    (``references/smallthinker.py::seen``). A group's key and value are
    read by its ``H / G`` heads in turn and never copied."""
    import jax
    import jax.numpy as jnp

    seen = _other("smallthinker").seen
    rows, t, heads, d = q.shape
    groups = k.shape[2]
    blk = min(t, ROW_BLOCK)
    at = jnp.arange(t)

    @jax.checkpoint
    def block(qb, kh, vh, start):
        s = jnp.einsum("btd,bsd->bts", rnd(qb), rnd(kh)) / math.sqrt(d)
        ok = seen(start + jnp.arange(blk), at, window)
        w = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", rnd(w), rnd(vh))

    def group(qkv):
        qg, kh, vh = qkv            # [H / G, rows, seq, D], [rows, seq, D]

        def head(qh):
            qb = jnp.moveaxis(qh.reshape(rows, t // blk, blk, d), 1, 0)
            out = jax.lax.map(lambda a: block(a[0], kh, vh, a[1]),
                              (qb, jnp.arange(t // blk) * blk))
            return jnp.moveaxis(out, 0, 1).reshape(rows, t, d)
        return jax.lax.map(head, qg)

    qg = jnp.moveaxis(q, 2, 0).reshape(groups, heads // groups, rows, t, d)
    out = jax.lax.map(group, (qg, jnp.moveaxis(k, 2, 0),
                              jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out.reshape(heads, rows, t, d), 0, 2)


def _attention(p, h, sliding: bool, spec, rnd):
    """(the attention's output [rows, seq, d], the mean square of a
    sliding layer's core output over the rows that see a whole window,
    before the gate; 0 for a full layer)."""
    import jax
    rows, t, _ = h.shape
    groups, d = spec["n_kv_head"], spec["head_dim"]
    heads = p["q"]["kernel"].shape[-1] // d     # this layer's own count
    hr = rnd(h)
    q = (hr @ rnd(p["q"]["kernel"])).reshape(rows, t, heads, d)
    k = (hr @ rnd(p["k"]["kernel"])).reshape(rows, t, groups, d)
    v = (hr @ rnd(p["v"]["kernel"])).reshape(rows, t, groups, d)
    inv, amplitude = positions(spec, sliding)
    q, k = (_rotate(z, inv, amplitude) for z in (q, k))
    o = _softmax_attention(q, k, v, spec["window"] if sliding else None,
                           rnd)
    whole = o[:, min(spec["window"], t) - 1:]
    g = jax.nn.sigmoid(hr @ rnd(p["g"]["kernel"]))      # [rows, seq, heads]
    y = rnd((o * g[..., None]).reshape(rows, t, heads * d)) @ rnd(
        p["out"]["kernel"])
    return y, (whole * whole).mean() if sliding else 0.0


def routes(p, h, spec):
    """(weights [rows, seq, k], experts [rows, seq, k]) of the sigmoid
    router, float32: ``s = sigmoid(h W_r)``, the ``top_k`` of ``s + b``,
    weights ``s_i / (sum s_i + 1e-20)`` times the scaling factor."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.sigmoid(h @ p["kernel"])
    _, chosen = jax.lax.top_k(scores + p["e_score_correction_bias"],
                              spec["top_k"])
    top = jnp.take_along_axis(scores, chosen, -1)
    if spec["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    return top * spec["route_scale"], chosen


def _moe(p, h, spec, rnd):
    """(the held experts' part of the routed sum plus the shared
    expert, the routes each of the E experts received [E]): every held
    expert on every token, times the token's weight for it or zero, one
    expert at a time into one sum."""
    import jax
    import jax.numpy as jnp

    first, held = spec["experts_held"]
    hr = rnd(h)
    weights, chosen = routes(p["gate"], hr, spec)
    load = jax.nn.one_hot(chosen, p["gate"]["kernel"].shape[-1]).sum(
        (0, 1, 2))

    @jax.checkpoint
    def one(y, expert):
        gate, up, down, e = expert
        w = jnp.where(chosen == e, weights, 0.0).sum(-1)
        a = jax.nn.silu(hr @ rnd(gate)) * (hr @ rnd(up))
        return y + (rnd(a) @ rnd(down)) * w[..., None], None

    ex = p["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        ex["gate_proj"], ex["up_proj"], ex["down_proj"],
        first + jnp.arange(held)))
    return y + _other("joyai")._swiglu(p["shared"], hr, rnd), load


def _halves(spec: dict, layer: int):
    """A block as its two residual halves, each differentiated alone:
    ``(p, x) -> (x + Attn(norm x), the mean square of a sliding core's
    output or 0)`` and ``(p, x) -> (x + MLP(norm x), the routes each of
    the E experts received, or zeros for the dense MLP)``."""
    import jax.numpy as jnp
    joyai = _other("joyai")
    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))
    eps = spec["rms_eps"]
    sliding = spec["layer_types"][layer] == SLIDING
    routed = spec["mlp_layer_types"][layer] == "sparse"

    def attn_half(p, x):
        mixed, out_sq = _attention(
            p["attn"], joyai._rms_norm(x, p["attn_norm"]["scale"], eps),
            sliding, spec, rnd)
        return x + mixed, out_sq

    def mlp_half(p, x):
        h = joyai._rms_norm(x, p["mlp_norm"]["scale"], eps)
        if not routed:
            return x + joyai._swiglu(p["mlp"], rnd(h), rnd), jnp.zeros(())
        y, load = _moe(p["mlp"], h, spec, rnd)
        return x + y, load
    return attn_half, mlp_half


def forward(params, tokens, spec: dict):
    """(logits [rows, seq, vocab], the routes per expert of each routed
    layer [L', E], the mean square of each sliding core's output): the
    whole forward pass in one piece, for tests at small sizes."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = params["wte"]["embedding"][tokens]
        loads, out_sq = [], []
        for i in range(spec["n_layer"]):
            attn_half, mlp_half = _halves(spec, i)
            x, sq = attn_half(params[f"h_{i}"], x)
            x, load = mlp_half(params[f"h_{i}"], x)
            if spec["mlp_layer_types"][i] == "sparse":
                loads.append(load)
            if spec["layer_types"][i] == SLIDING:
                out_sq.append(sq)
        logits = _other("joyai")._rms_norm(
            x, params["norm_f"]["scale"],
            spec["rms_eps"]) @ params["lm_head"]["kernel"]
    return logits, jnp.stack(loads), jnp.stack(out_sq)


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the numbers, the gradient tree or None, the routes per expert
    [L', E]) of the whole batch at ``params``, float32 throughout.
    ``batch`` is {"tokens", "targets"}, [rows, seq]. ``spec``: n_layer,
    layer_types and mlp_layer_types (the published lists), n_kv_head,
    head_dim, window, sliding_theta, full_theta, rotated_lanes, yarn
    {factor, original_len, beta_fast, beta_slow, attention_factor},
    top_k, norm_topk_prob, route_scale, experts_held (first, count),
    rms_eps, and for the low reading operand_dtype. ``params`` may be
    numpy's, on the host: a block's are on the device while it runs.
    A block is differentiated half by half (attention, then the MLP),
    each from its output's cotangent. Without ``keep_grads`` a half's
    gradient lives only until its squared norm is taken; the kept tree
    is numpy's, on the host."""
    import jax
    import jax.numpy as jnp

    def on_device(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, jnp.float32), tree)

    def part(i, half):
        """The parameters of layer ``i`` that ``half`` (0 attention, 1
        the MLP) reads."""
        names = ("attn_norm", "attn") if half == 0 else ("mlp_norm", "mlp")
        return on_device({k: params[f"h_{i}"][k] for k in names})

    tokens, targets = batch["tokens"], batch["targets"]
    kinds = {}      # layers of one kind and head count share four programs

    def programs(i):
        key = (spec["layer_types"][i], spec["mlp_layer_types"][i],
               params[f"h_{i}"]["attn"]["q"]["kernel"].shape)
        if key not in kinds:
            kinds[key] = [(jax.jit(f), jax.jit(
                lambda p, x, dx, f=f: jax.vjp(
                    lambda *a: f(*a)[0], p, x)[1](dx)))
                for f in _halves(spec, i)]
        return kinds[key]

    grads, squares = {}, []

    def took(name, g):
        squares.extend(float(jnp.sum(z * z))
                       for z in jax.tree_util.tree_leaves(g))
        if keep_grads:
            grads.setdefault(name, {}).update(jax.device_get(g))

    with jax.default_matmul_precision("highest"):
        x = on_device(params["wte"]["embedding"])[tokens]
        inputs, loads, out_sq = [], [], []
        for i in range(spec["n_layer"]):
            for half in (0, 1):
                inputs.append(x)
                x, said = programs(i)[half][0](part(i, half), x)
                if half == 0 and spec["layer_types"][i] == SLIDING:
                    out_sq.append(float(said))
                if half == 1 and spec["mlp_layer_types"][i] == "sparse":
                    loads.append(said)
        loss, (g_norm, g_head, dx) = jax.jit(jax.value_and_grad(
            _other("smallthinker")._tail(spec), argnums=(0, 1, 2)))(
                on_device(params["norm_f"]["scale"]),
                on_device(params["lm_head"]["kernel"]), x, targets)
        took("norm_f", {"scale": g_norm})
        took("lm_head", {"kernel": g_head})
        del g_head, x
        for i in reversed(range(spec["n_layer"])):
            for half in (1, 0):
                g, dx = programs(i)[half][1](part(i, half), inputs.pop(), dx)
                took(f"h_{i}", g)
                del g
        took("wte", {"embedding": jnp.zeros(
            params["wte"]["embedding"].shape, jnp.float32).at[tokens].add(
                dx)})
    first, held = spec["experts_held"]
    load = jnp.stack(loads)
    out = {"loss": float(loss),
           "grad_norm": math.sqrt(sum(squares)),
           "moe_absent_route_share": 1.0 - float(
               load[:, first:first + held].sum() / load.sum()),
           "attn_window_out_rms": math.sqrt(sum(out_sq) / len(out_sq))}
    return out, (grads if keep_grads else None), load


def loss_and_grad_norm(params, batch, spec: dict, load=None) -> dict:
    """{"loss", "grad_norm", "moe_absent_route_share",
    "attn_window_out_rms"} and, given ``spec["adamw"]``,
    ``"update_norm"``: ``loop.py`` holds every key against the metric of
    that name of the program's first dispatch, all at the
    configuration's one ``rtol``. The routing statistic is the share of
    routes that land on **absent** experts (seven eighths at an even
    load with 32 of 256 held); ``attn_window_out_rms`` is the root mean
    square of the sliding cores' output, before the gate, over the rows
    that see a whole window. A list given as ``load`` receives a row a
    routed layer of the routes each expert drew."""
    adamw = spec.get("adamw")
    out, grads, routes = loss_and_grads(params, batch, spec,
                                        keep_grads=bool(adamw))
    if load is not None:
        load.extend(routes.tolist())
    if adamw:
        out["update_norm"] = _other("joyai").adamw_first_change(
            params, grads, out["grad_norm"], adamw)
    return out

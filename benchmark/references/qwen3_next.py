"""Qwen3-Next (three Gated DeltaNet layers to one gated softmax-attention
layer, every block a softmax-routed SwiGLU mixture beside a gated shared
expert, zero-centred RMSNorms, an untied head) as plain ``jax.numpy`` in
float32: the configuration's plain reference. It shares no code with
``ray_tpu/``: it reads the program's parameter tree and the same batch
and computes the model the straightforward way, from the layer equations
(``configs/qwen3-next-80b-a3b.json`` repeats them):

- **norms** ``x / sqrt(mean(x^2) + eps) * (1 + w)``: a block's two, the
  final one, and the attention layer's per-head q/k norms; the Gated
  DeltaNet's output norm is the plain ``* w``;
- **Gated DeltaNet as the recurrence itself**, one row at a time: ``S <-
  exp(g_t) S``; ``u = beta_t (v_t - S^T k_t)``; ``S <- S + k_t u^T``;
  ``o_t = S^T q_t``, as elementwise products and sums over a ``[H, K,
  V]`` state, ``g_t`` one number a head. No chunk, no WY form, no
  running sum of decays: a fault in the program's chunk algebra cannot
  be in here too. Value head ``j`` reads key head ``j // (H / Hk)`` by
  an index, never by a reshaped product. Kept whole the scan would save
  a state a row, so it is nested (``references/kimi_linear.py``: an
  outer scan over blocks of ``TOKEN_BLOCK`` rows whose body is a
  ``jax.checkpoint`` of an inner scan of the same per-row update):
  bookkeeping, not algebra;
- the convolution as **shifted sums** and a SiLU
  (``references/kimi_linear.py::_conv_silu``) over the 8,192 channels
  ``[q | k | v]``; a head's q and k to unit length, q times ``K^-1/2``;
  ``g = -exp(A_log) softplus(a + dt_bias)``, ``beta = sigmoid(b)``; ``y
  = W_out (silu(z) * RMSNorm_head(o) * w)``;
- **attention as a masked softmax**: q and the gate from two matrices,
  k and v over 2 heads; q and k normed a head (zero-centred), their
  first ``rotated_lanes`` lanes rotated in halves
  (``references/laguna.py::_rotate``), a masked softmax a head and a
  block of score rows at a time with a group's K and V read by its 8
  heads in turn (``references/laguna.py::_softmax_attention``), the
  output times ``sigmoid(gate)`` lane by lane;
- the routed layer with **every held expert on every token** times the
  token's weight for it or zero: ``p = softmax(h W_r)`` over all 512,
  the ``top_k`` largest divided by their sum; the shared expert times
  ``sigmoid(h w_g)``; the loss a chunk of rows at a time
  (``references/smallthinker.py::_tail``).

It runs on the chip after the window, beside the live train state, so
it is frugal with memory and not with time: the gradient is taken **a
block's half at a time** (the mixer, then the MLP, each from its
output's cotangent, as ``references/laguna.py``), a Gated DeltaNet
layer's key heads ``HEAD_GROUP`` at a time, and the parameters may wait
on the host (numpy): a half's are on the device only while it runs.

Beside the loss, the gradient's norm and the absent routes' share it
returns ``gdn_out_rms``, the root mean square of the recurrences' output
``o`` over the Gated DeltaNet layers (before norm and gate), and a key a
group of ``spec["grad_groups"]`` (``grad_norm_gdn_gates``,
``grad_norm_attn_qk``). ``spec["adamw"]`` adds the optimizer's first
step and ``update_norm``; ``spec["operand_dtype"]`` (absent in a run of
the benchmark) gives the reading that the configuration's limit is set
against from below: every matmul operand that the program holds in its
compute type rounded to that type first, the router's matmul and the
recurrence (which the program runs in float32) left alone.
"""

from __future__ import annotations

import math
import re

TOKEN_BLOCK = 128       # rows of one recomputed block of the recurrence
HEAD_GROUP = 8          # key heads of a Gated DeltaNet layer at a time


def _other(name: str):
    from benchlib import manifest
    return manifest.load_reference(name)


def _norm(x, scale, eps):
    """The zero-centred RMSNorm over the last axis."""
    import jax.numpy as jnp
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + scale)


def recurrence(q, k, v, g, beta):
    """``o`` [rows, seq, H, V] of the gated delta rule with a decay a
    head, row by row. q, k [rows, seq, Hk, K]; v [rows, seq, H, V]; g,
    beta [rows, seq, H]; value head ``j`` reads key head ``j // (H /
    Hk)``."""
    import jax
    import jax.numpy as jnp

    rows, t, heads, vd = v.shape
    key_of = jnp.arange(heads) // (heads // q.shape[2])

    def token(S, row):
        q, k, v, g, beta = row                          # [rows, H, .]
        q, k = q[:, key_of], k[:, key_of]
        S = jnp.exp(g)[..., None, None] * S             # exp(g_t) S
        u = beta[..., None] * (v - jnp.sum(k[..., None] * S, -2))
        S = S + k[..., None] * u[..., None, :]          # + k u^T
        return S, jnp.sum(q[..., None] * S, -2)         # S^T q

    @jax.checkpoint
    def block(S, rows_):
        return jax.lax.scan(token, S, rows_, unroll=8)

    size = math.gcd(t, TOKEN_BLOCK)
    per_block = tuple(
        jnp.moveaxis(z, 1, 0).reshape(t // size, size, *z.shape[:1],
                                      *z.shape[2:])
        for z in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        block, jnp.zeros((rows, heads, q.shape[-1], vd), jnp.float32),
        per_block)
    return jnp.moveaxis(o.reshape(t, rows, heads, vd), 0, 1)


def _gdn(p, h, spec, rnd):
    """(the mixer's output, the mean square of the recurrence's). The
    key heads are independent between the input and the sum that
    ``W_out`` makes over the heads, so they are walked ``HEAD_GROUP`` at
    a time under ``jax.checkpoint``, each group with its own columns of
    the weights (a key head's, and its value heads'): bookkeeping, so
    that a layer's float32 intermediates fit beside the live state."""
    import jax
    import jax.numpy as jnp

    conv_silu = _other("kimi_linear")._conv_silu
    rows, t, d = h.shape
    key_heads, heads = spec["gdn_key_heads"], spec["gdn_value_heads"]
    kd = p["norm"].shape[0]
    keys, inner = key_heads * kd, heads * kd
    size = math.gcd(key_heads, HEAD_GROUP)
    groups = key_heads // size
    h = rnd(h)

    def columns(w):         # [.., n] -> [groups, .., n / groups]
        return jnp.moveaxis(w.reshape(*w.shape[:-1], groups, -1), -2, 0)

    w_in, conv, w_ba = p["qkvz"]["kernel"], p["conv"], p["ba"]["kernel"]
    parts = {"q": (0, keys), "k": (keys, 2 * keys),
             "v": (2 * keys, 2 * keys + inner)}
    per_group = {
        **{n: columns(w_in[:, a:b]) for n, (a, b) in parts.items()},
        **{f"{n}_conv": columns(conv[:, a:b]) for n, (a, b) in parts.items()},
        "z": columns(w_in[:, 2 * keys + inner:]),
        "b": columns(w_ba[:, :heads]), "a": columns(w_ba[:, heads:]),
        "A_log": columns(p["A_log"]), "dt_bias": columns(p["dt_bias"]),
        "out": p["out"]["kernel"].reshape(groups, -1, d)}

    def unit(x, n):
        x = x.reshape(rows, t, n, kd)
        return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    @jax.checkpoint
    def group(w):
        q, k, v = (conv_silu(h @ rnd(w[n]), w[f"{n}_conv"]) for n in "qkv")
        n_v = v.shape[-1] // kd
        g = -jnp.exp(w["A_log"]) * jax.nn.softplus(
            h @ rnd(w["a"]) + w["dt_bias"])
        beta = jax.nn.sigmoid(h @ rnd(w["b"]))
        o = recurrence(unit(q, size) * kd ** -0.5, unit(k, size),
                       v.reshape(rows, t, n_v, kd), g, beta)
        normed = o / jnp.sqrt((o * o).mean(-1, keepdims=True)
                              + spec["rms_eps"]) * p["norm"]
        y = jax.nn.silu(h @ rnd(w["z"])) * normed.reshape(rows, t, -1)
        return rnd(y) @ rnd(w["out"]), (o * o).sum()

    y, sq = jax.lax.map(group, per_group)
    return y.sum(0), sq.sum() / (rows * t * inner)


def _attention(p, h, spec, rnd):
    """The gated attention layer's output [rows, seq, d]."""
    import jax
    import jax.numpy as jnp

    laguna = _other("laguna")
    rows, t, _ = h.shape
    heads, groups, d = spec["n_head"], spec["n_kv_head"], spec["head_dim"]
    eps, r = spec["rms_eps"], spec["rotated_lanes"]
    hr = rnd(h)
    q = (hr @ rnd(p["q"]["kernel"])).reshape(rows, t, heads, d)
    k = (hr @ rnd(p["k"]["kernel"])).reshape(rows, t, groups, d)
    v = (hr @ rnd(p["v"]["kernel"])).reshape(rows, t, groups, d)
    q, k = _norm(q, p["q_norm"], eps), _norm(k, p["k_norm"], eps)
    inv = spec["rope_theta"] ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    q, k = (laguna._rotate(z, inv, 1.0) for z in (q, k))
    o = laguna._softmax_attention(q, k, v, None, rnd)
    gate = jax.nn.sigmoid(hr @ rnd(p["gate"]["kernel"]))
    return rnd(o.reshape(rows, t, heads * d) * gate) @ rnd(p["out"]["kernel"])


def routes(router_w, h, spec):
    """(weights [rows, seq, k], experts [rows, seq, k]) of the softmax
    router, float32: ``p = softmax(h W_r)`` over every expert, the
    ``top_k`` largest, divided by their sum under ``norm_topk_prob``."""
    import jax
    probs = jax.nn.softmax(h @ router_w, axis=-1)
    top, chosen = jax.lax.top_k(probs, spec["top_k"])
    if spec["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    return top, chosen


def experts_part(ex, h, weights, chosen, held, rnd):
    """The part of the routed sum that experts ``held = (first, count)``
    give, ``ex`` holding their matrices: every one of them on every
    token, times the token's weight for it or zero, one expert at a
    time into one sum."""
    import jax
    import jax.numpy as jnp

    first, count = held
    hr = rnd(h)

    @jax.checkpoint
    def one(y, expert):
        gate, up, down, e = expert
        w = jnp.where(chosen == e, weights, 0.0).sum(-1)
        a = jax.nn.silu(hr @ rnd(gate)) * (hr @ rnd(up))
        return y + (rnd(a) @ rnd(down)) * w[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        ex["gate_proj"], ex["up_proj"], ex["down_proj"],
        first + jnp.arange(count)))
    return y


def _moe(p, h, spec, rnd):
    """(the held experts' part of the routed sum plus the gated shared
    expert, the routes each of the E experts received [E])."""
    import jax

    hr = rnd(h)
    weights, chosen = routes(p["gate"]["kernel"], hr, spec)
    load = jax.nn.one_hot(chosen, p["gate"]["kernel"].shape[-1]).sum(
        (0, 1, 2))
    y = experts_part(p["experts"], h, weights, chosen, spec["experts_held"],
                     rnd)
    shared = _other("joyai")._swiglu(p["shared"], hr, rnd)
    gate = jax.nn.sigmoid(hr @ rnd(p["shared_gate"]["kernel"]))
    return y + gate * shared, load


def mixer_of(spec: dict, layer: int) -> str:
    """``F`` or ``L`` for ``layer`` counted from 0."""
    return "F" if (layer + 1) % spec["full_attention_interval"] == 0 else "L"


def _halves(spec: dict, layer: int):
    """A block as its two residual halves, each differentiated alone:
    ``(p, x) -> (x + Mixer(norm x), the mean square of the recurrence's
    output or 0)`` and ``(p, x) -> (x + MoE(norm x), the routes each of
    the E experts received)``."""
    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))
    eps = spec["rms_eps"]
    kind = mixer_of(spec, layer)

    def mixer_half(p, x):
        h = _norm(x, p["attn_norm"]["scale"], eps)
        if kind == "F":
            return x + _attention(p["attn"], h, spec, rnd), 0.0
        mixed, out_sq = _gdn(p["gdn"], h, spec, rnd)
        return x + mixed, out_sq

    def mlp_half(p, x):
        y, load = _moe(p["mlp"], _norm(x, p["mlp_norm"]["scale"], eps),
                       spec, rnd)
        return x + y, load
    return mixer_half, mlp_half


def _tail(spec: dict):
    """``references/smallthinker.py::_tail`` under the zero-centred
    final norm."""
    plain = _other("smallthinker")._tail(spec)
    return lambda scale, head, x, targets: plain(1.0 + scale, head, x,
                                                 targets)


def forward(params, tokens, spec: dict):
    """(logits [rows, seq, vocab], the routes per expert of each layer
    [L, E], the mean square of each Gated DeltaNet layer's ``o``): the
    whole forward pass in one piece, for tests at small sizes."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = params["wte"]["embedding"][tokens]
        loads, out_sq = [], []
        for i in range(spec["n_layer"]):
            mixer_half, mlp_half = _halves(spec, i)
            x, sq = mixer_half(params[f"h_{i}"], x)
            x, load = mlp_half(params[f"h_{i}"], x)
            loads.append(load)
            if mixer_of(spec, i) == "L":
                out_sq.append(sq)
        logits = _norm(x, params["norm_f"]["scale"],
                       spec["rms_eps"]) @ params["lm_head"]["kernel"]
    return logits, jnp.stack(loads), jnp.stack(out_sq)


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the numbers, the gradient tree or None, the routes per expert
    [L, E]) of the whole batch at ``params``, float32 throughout.
    ``batch`` is {"tokens", "targets"}, [rows, seq]. ``spec``: n_layer,
    full_attention_interval, gdn_key_heads, gdn_value_heads, n_head,
    n_kv_head, head_dim, rotated_lanes, rope_theta, top_k,
    norm_topk_prob, experts_held (first, count), rms_eps, for the low
    reading operand_dtype, and ``grad_groups`` {name: regular expression
    over a gradient leaf's path, ``h_1/gdn/A_log``}: the norm of the
    leaves each finds is among the numbers under its name. ``params``
    may be numpy's, on the host: a half's are on the device while it
    runs. Without ``keep_grads`` a half's gradient lives only until its
    squared norm is taken; the kept tree is numpy's, on the host."""
    import jax
    import jax.numpy as jnp

    def on_device(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, jnp.float32), tree)

    def part(i, half):
        """The parameters of layer ``i`` that ``half`` (0 the mixer, 1
        the MLP) reads."""
        mixer = "attn" if mixer_of(spec, i) == "F" else "gdn"
        names = ("attn_norm", mixer) if half == 0 else ("mlp_norm", "mlp")
        return on_device({k: params[f"h_{i}"][k] for k in names})

    tokens, targets = batch["tokens"], batch["targets"]
    kinds = {}      # layers of one kind share their four programs

    def programs(i):
        key = mixer_of(spec, i)
        if key not in kinds:
            kinds[key] = [(jax.jit(f), jax.jit(
                lambda p, x, dx, f=f: jax.vjp(
                    lambda *a: f(*a)[0], p, x)[1](dx)))
                for f in _halves(spec, i)]
        return kinds[key]

    grads, squares = {}, {}     # squares: a leaf's path -> its squared norm

    def took(name, g):
        for path, z in jax.tree_util.tree_flatten_with_path(g)[0]:
            squares["/".join([name, *(k.key for k in path)])] = float(
                jnp.sum(z * z))
        if keep_grads:
            grads.setdefault(name, {}).update(jax.device_get(g))

    with jax.default_matmul_precision("highest"):
        x = on_device(params["wte"]["embedding"])[tokens]
        inputs, loads, out_sq = [], [], []
        for i in range(spec["n_layer"]):
            for half in (0, 1):
                inputs.append(x)
                x, said = programs(i)[half][0](part(i, half), x)
                if half == 1:
                    loads.append(said)
                elif mixer_of(spec, i) == "L":
                    out_sq.append(float(said))
        loss, (g_norm, g_head, dx) = jax.jit(jax.value_and_grad(
            _tail(spec), argnums=(0, 1, 2)))(
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
           "grad_norm": math.sqrt(sum(squares.values())),
           "moe_absent_route_share": 1.0 - float(
               load[:, first:first + held].sum() / load.sum()),
           "gdn_out_rms": math.sqrt(sum(out_sq) / len(out_sq))}
    for name, pattern in spec.get("grad_groups", {}).items():
        out[name] = math.sqrt(sum(
            sq for path, sq in squares.items() if re.search(pattern, path)))
    return out, (grads if keep_grads else None), load


def loss_and_grad_norm(params, batch, spec: dict, load=None) -> dict:
    """{"loss", "grad_norm", "moe_absent_route_share", "gdn_out_rms"}, a
    key a group of ``spec["grad_groups"]`` and, given ``spec["adamw"]``,
    ``"update_norm"``: ``loop.py`` holds every key against the metric of
    that name of the program's first dispatch, all at the
    configuration's one ``rtol``. The routing statistic is the share of
    routes that land on **absent** experts (15/16 at an even load with
    32 of 512 held). A list given as ``load`` receives a row a layer of
    the routes each expert drew."""
    adamw = spec.get("adamw")
    out, grads, routes_ = loss_and_grads(params, batch, spec,
                                         keep_grads=bool(adamw))
    if load is not None:
        load.extend(routes_.tolist())
    if adamw:
        out["update_norm"] = _other("joyai").adamw_first_change(
            params, grads, out["grad_norm"], adamw)
    return out

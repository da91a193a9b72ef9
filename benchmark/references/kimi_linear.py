"""Kimi-Linear (three Kimi Delta Attention layers to one latent-attention
layer without positions, a leading dense MLP, sigmoid-routed SwiGLU
experts beside a shared one, an untied head) as plain ``jax.numpy`` in
float32: the configuration's plain reference. It shares no code with
``ray_tpu/``: it reads the program's parameter tree and the same batch
and computes the model the straightforward way, from the layer equations
(``configs/kimi-linear-48b-a3b.json`` repeats them):

- **KDA as the recurrence itself**, one token at a time: ``S <-
  Diag(alpha_t) S``; ``S <- S - beta_t k_t (k_t^T S)``; ``S <- S +
  beta_t k_t v_t^T``; ``o_t = S^T q_t``, exactly in that order, as
  elementwise products and sums over a ``[H, K, V]`` state. No chunk, no
  WY form, no running sum of decays: a fault in the program's chunk
  algebra cannot be in here too. Its gradients come from reverse mode
  through that scan; kept whole it would save a state a token (34 GB a
  layer at 16,384 rows), so the scan is nested: an outer scan over
  blocks of ``TOKEN_BLOCK`` tokens whose body is a ``jax.checkpoint`` of
  an inner scan of *the same per-token update*; only block-boundary
  states are kept. That is bookkeeping, not algebra;
- the three convolutions as **shifted sums** (``y_t = sum_j w_j x_{t - 3
  + j}``) and a SiLU; a head's q and k to unit length, q times
  ``K^-1/2``; ``g = -exp(A_log) softplus(W_f2 (W_f1 h) + dt_bias)``;
  ``beta = sigmoid(W_b h)``; ``y = W_o (sigmoid(W_g2 (W_g1 h) + b_g) *
  RMSNorm_head(o))``;
- latent attention with **the keys concatenated and nothing rotated**:
  head ``i``'s query is ``[q_nope_i | q_rope_i]`` straight from ``W_q
  h``, its key ``[k_nope_i | k_r]``, 192 wide, the shared key copied to
  every head; a masked softmax a head and a block of query rows at a
  time so that ``[rows, 16384]`` fits; values 128 wide;
- the routed layer and the shared expert as ``references/joyai.py`` has
  them (every held expert on every token times its route's weight or
  zero; the same sigmoid router), the dense MLP plain SwiGLU; the loss
  a chunk of rows at a time (``references/smallthinker.py::_tail``).

It runs on the chip after the window, beside the live train state, so
it is frugal with memory and not with time (16,384 dependent steps a
group of KDA heads, twice, once a run, outside the window): the gradient
is taken **a layer at a time** as ``references/joyai.py`` takes it, a
KDA layer's heads sixteen at a time, and the parameters may wait on the
host (numpy): a block's are on the device only while the block runs.

Beside the loss, the gradient's norm and the absent routes' share it
returns **two numbers of the new mechanism's own**: ``kda_out_rms``, the
root mean square of the recurrences' output ``o`` over the KDA layers
(before norm and gate), and a key a group of ``spec["grad_groups"]``
(``grad_norm_kda_gates``: the decays' and step sizes' parameters).
``spec["adamw"]`` adds the optimizer's first step
(``references/joyai.py::adamw_first_change``) and ``update_norm``;
``spec["operand_dtype"]`` (absent in a run of the benchmark) gives the
reading that the configuration's limit is set against from below: every
matmul operand that the program holds in its compute type rounded to
that type first, the router's matmul and the recurrence (which the
program runs in float32) left alone.
"""

from __future__ import annotations

import math
import re

ROW_BLOCK = 2048        # score rows computed at a time
TOKEN_BLOCK = 128       # tokens of one recomputed block of the recurrence
HEAD_GROUP = 16         # KDA heads computed at a time


def _other(name: str):
    from benchlib import manifest
    return manifest.load_reference(name)


def _conv_silu(x, w):
    """silu(y), ``y[t] = sum_j w[j] x[t - (K - 1) + j]``, zeros before
    the start: x [rows, seq, C], w [K, C]."""
    import jax
    import jax.numpy as jnp
    taps, t = w.shape[0], x.shape[1]
    y = 0.0
    for j in range(taps):
        back = taps - 1 - j
        y = y + w[j] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
    return jax.nn.silu(y)


def recurrence(q, k, v, g, beta):
    """``o`` [rows, seq, H, V] of the gated delta rule, token by token.
    q, k, g [rows, seq, H, K]; v [rows, seq, H, V]; beta [rows, seq, H]."""
    import jax
    import jax.numpy as jnp

    rows, t, heads, kd = q.shape

    def token(S, row):
        q, k, v, g, beta = row                          # [rows, H, .]
        S = jnp.exp(g)[..., None] * S                   # Diag(alpha_t) S
        kb = beta[..., None] * k
        S = S - kb[..., None] * jnp.sum(k[..., None] * S, -2)[..., None, :]
        S = S + kb[..., None] * v[..., None, :]         # + beta k v^T
        return S, jnp.sum(q[..., None] * S, -2)         # S^T q

    @jax.checkpoint
    def block(S, rows_):
        # unrolled: eight tokens a turn of the device's loop (bookkeeping)
        return jax.lax.scan(token, S, rows_, unroll=8)

    size = math.gcd(t, TOKEN_BLOCK)
    per_block = tuple(
        jnp.moveaxis(z, 1, 0).reshape(t // size, size, *z.shape[:1],
                                      *z.shape[2:])
        for z in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        block, jnp.zeros((rows, heads, kd, v.shape[-1]), jnp.float32),
        per_block)
    return jnp.moveaxis(o.reshape(t, rows, heads, -1), 0, 1)


def _kda(p, h, spec, rnd):
    """(the mixer's output, the mean square of the recurrence's). The
    heads are independent between ``W_f1 h``, ``W_g1 h`` and the sum
    that ``W_o`` makes over them, so they are walked ``HEAD_GROUP`` at a
    time under ``jax.checkpoint``, each group with its own columns of
    the weights: bookkeeping, so that a layer's float32 intermediates
    (some twenty arrays of [seq, 4096]) fit beside the live train
    state."""
    import jax
    import jax.numpy as jnp

    rows, t, d = h.shape
    heads = spec["kda_heads"]
    size = math.gcd(heads, HEAD_GROUP)
    groups = heads // size
    h = rnd(h)
    f_low = rnd(h @ rnd(p["f_a"]["kernel"]))
    g_low = rnd(h @ rnd(p["g_a"]["kernel"]))

    def columns(w):         # [.., heads * K] -> [groups, .., size * K]
        return jnp.moveaxis(w.reshape(*w.shape[:-1], groups, -1), -2, 0)

    per_group = {
        **{n: columns(p[n]["kernel"]) for n in "qkvb"},
        **{n: columns(p[n]) for n in ("q_conv", "k_conv", "v_conv", "f_b",
                                      "dt_bias", "g_b", "g_bias", "A_log")},
        "out": p["out"]["kernel"].reshape(groups, -1, d)}

    def unit(x):
        x = x.reshape(rows, t, size, -1)
        return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    @jax.checkpoint
    def group(w):
        q, k, v = (_conv_silu(h @ rnd(w[n]), w[f"{n}_conv"]) for n in "qkv")
        q, k = unit(q), unit(k)
        kd = q.shape[-1]
        f = f_low @ rnd(w["f_b"]) + w["dt_bias"]
        g = (-jnp.exp(w["A_log"])[:, None]
             * jax.nn.softplus(f).reshape(rows, t, size, kd))
        beta = jax.nn.sigmoid(h @ rnd(w["b"]))
        o = recurrence(q * kd ** -0.5, k, v.reshape(rows, t, size, kd), g,
                       beta)
        gate = jax.nn.sigmoid(g_low @ rnd(w["g_b"]) + w["g_bias"])
        normed = o / jnp.sqrt((o * o).mean(-1, keepdims=True)
                              + spec["rms_eps"]) * p["norm"]
        y = gate * normed.reshape(rows, t, -1)
        return rnd(y) @ rnd(w["out"]), (o * o).sum()

    y, sq = jax.lax.map(group, per_group)
    return y.sum(0), sq.sum() / (rows * t * heads * p["norm"].shape[0])


def _mla(p, h, spec, rnd):
    """Latent attention without a query latent and without positions."""
    import jax
    import jax.numpy as jnp

    rows, t, _ = h.shape
    heads, dn, dr = spec["n_head"], spec["nope_dim"], spec["rope_dim"]
    rms = _other("joyai")._rms_norm
    h = rnd(h)
    kv = h @ rnd(p["kv_down"]["proj"]["kernel"])
    c_kv = rnd(rms(kv[..., :spec["kv_rank"]], p["kv_down"]["norm"]["scale"],
                   spec["rms_eps"]))
    k_r = kv[..., spec["kv_rank"]:]
    q = jnp.concatenate([
        (h @ rnd(p["q_up"]["nope"])).reshape(rows, t, heads, dn),
        (h @ rnd(p["q_up"]["rope"])).reshape(rows, t, heads, dr)], -1)
    k = jnp.concatenate([
        (c_kv @ rnd(p["kv_up"]["k"])).reshape(rows, t, heads, dn),
        jnp.broadcast_to(k_r[:, :, None], (rows, t, heads, dr))], -1)
    v = (c_kv @ rnd(p["kv_up"]["v"])).reshape(rows, t, heads, -1)
    blk = min(t, ROW_BLOCK)
    at = jnp.arange(t)

    @jax.checkpoint
    def block(qb, kh, vh, start):
        s = jnp.einsum("btd,bsd->bts", rnd(qb), rnd(kh)) / math.sqrt(dn + dr)
        seen = at[None, :] <= (start + jnp.arange(blk))[:, None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", rnd(w), rnd(vh))

    def head(qkv):
        qh, kh, vh = qkv                                # [rows, seq, width]
        qb = jnp.moveaxis(qh.reshape(rows, t // blk, blk, -1), 1, 0)
        out = jax.lax.map(lambda a: block(a[0], kh, vh, a[1]),
                          (qb, jnp.arange(t // blk) * blk))
        return jnp.moveaxis(out, 0, 1).reshape(rows, t, -1)

    y = jax.lax.map(head, tuple(jnp.moveaxis(z, 2, 0) for z in (q, k, v)))
    y = jnp.moveaxis(y, 0, 2).reshape(rows, t, -1)
    return rnd(y) @ rnd(p["out_proj"]["kernel"])


def mixer_of(spec: dict, layer: int) -> str:
    """``M`` or ``K`` for ``layer`` counted from 0; ``mla_layers`` count
    from 1, as the published list does."""
    return "M" if layer + 1 in spec["mla_layers"] else "K"


def _block(spec: dict, layer: int):
    """(p, x) -> (x, the routes each of the E experts received or None,
    the mean square of the recurrence's output or None)."""
    joyai = _other("joyai")
    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))
    eps = spec["rms_eps"]
    kind, routed = mixer_of(spec, layer), layer >= spec["dense_layers"]

    def block(p, x):
        h = joyai._rms_norm(x, p["attn_norm"]["scale"], eps)
        if kind == "M":
            mixed, out_sq = _mla(p["attn"], h, spec, rnd), None
        else:
            mixed, out_sq = _kda(p["kda"], h, spec, rnd)
        x = x + mixed
        h = joyai._rms_norm(x, p["mlp_norm"]["scale"], eps)
        if not routed:
            return x + joyai._swiglu(p["mlp"], rnd(h), rnd), None, out_sq
        y, load = joyai._moe(p["mlp"], h, spec, rnd)
        return x + y, load, out_sq
    return block


def forward(params, tokens, spec: dict):
    """(logits [rows, seq, vocab], the routes per expert of each routed
    layer [L', E], the mean square of each KDA layer's ``o``): the whole
    forward pass in one piece, for tests at small sizes."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = params["wte"]["embedding"][tokens]
        loads, out_sq = [], []
        for i in range(spec["n_layer"]):
            x, load, sq = _block(spec, i)(params[f"h_{i}"], x)
            if load is not None:
                loads.append(load)
            if sq is not None:
                out_sq.append(sq)
        logits = _other("joyai")._rms_norm(
            x, params["norm_f"]["scale"],
            spec["rms_eps"]) @ params["lm_head"]["kernel"]
    return logits, jnp.stack(loads), jnp.stack(out_sq)


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the numbers, the gradient tree or None, the routes per expert
    [L', E]) of the whole batch at ``params``, float32 throughout.
    ``batch`` is {"tokens", "targets"}, [rows, seq]. ``spec``: n_layer,
    dense_layers, mla_layers, kda_heads, n_head, kv_rank, nope_dim,
    rope_dim, top_k, norm_topk_prob, route_scale, experts_held (first,
    count), rms_eps, for the low reading operand_dtype, and
    ``grad_groups`` {name: regular expression over a gradient leaf's
    path, ``h_1/kda/A_log``}: the norm of the leaves each finds is among
    the numbers under its name. Without ``keep_grads`` a block's
    gradient lives only until its squared norm is taken; the kept tree
    is numpy's, on the host."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    tokens, targets = batch["tokens"], batch["targets"]
    kinds = {}      # layers of one kind share their two programs

    def programs(i):
        key = (mixer_of(spec, i), i >= spec["dense_layers"])
        if key not in kinds:
            block = _block(spec, i)
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
            x, load, sq = programs(i)[0](params[f"h_{i}"], x)
            if load is not None:
                loads.append(load)
            if sq is not None:
                out_sq.append(float(sq))
        loss, (g_norm, g_head, dx) = jax.jit(jax.value_and_grad(
            _other("smallthinker")._tail(spec), argnums=(0, 1, 2)))(
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
           "kda_out_rms": math.sqrt(sum(out_sq) / len(out_sq))}
    for name, pattern in spec.get("grad_groups", {}).items():
        out[name] = math.sqrt(sum(
            sq for path, sq in squares.items() if re.search(pattern, path)))
    return out, (grads if keep_grads else None), load


def loss_and_grad_norm(params, batch, spec: dict, load=None) -> dict:
    """{"loss", "grad_norm", "moe_absent_route_share", "kda_out_rms"}, a
    key a group of ``spec["grad_groups"]`` and, given ``spec["adamw"]``,
    ``"update_norm"``: ``loop.py`` holds every key against the metric of
    that name of the program's first dispatch, all at the
    configuration's one ``rtol``. The routing statistic is the share of
    routes that land on **absent** experts (31/32 at an even load with 8
    of 256 held). A list given as ``load`` receives a row a routed layer
    of the routes each expert drew."""
    adamw = spec.get("adamw")
    out, grads, routes = loss_and_grads(params, batch, spec,
                                        keep_grads=bool(adamw))
    if load is not None:
        load.extend(routes.tolist())
    if adamw:
        out["update_norm"] = _other("joyai").adamw_first_change(
            params, grads, out["grad_norm"], adamw)
    return out

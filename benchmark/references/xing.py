"""Xing4.0-29B-A4B (a DeepSeek-V3-shaped stack under manifold-constrained
hyper-connections) as plain ``jax.numpy`` in float32: the
configuration's plain reference. It shares no code with ``ray_tpu/``: it
reads the program's parameter tree and the same batch and computes the
model the straightforward way, from the layer equations
(``configs/xing4.0-29b-a4b.json`` repeats them):

- **the residual path**: a token's state is ``X`` [n, d], here an array
  ``[rows, seq, n, d]`` (the program's ``[rows, seq, n d]`` reshaped:
  stream ``i`` is its lanes ``[i d, (i + 1) d)``). For each sub-layer,
  with ``x = vec(X)``: ``m = (x / sqrt(mean(x^2) + 1e-6)) phi``;
  ``H_pre = sigmoid(alpha_0 m[0:n] + b[0:n])``; ``H_post = 2
  sigmoid(alpha_1 m[n:2n] + b[n:2n])``; ``A = clip(alpha_2 mat(m[2n:]) +
  mat(b[2n:]), -30, 30)``, ``M = exp(A)`` and **the Sinkhorn loop
  written out**, 20 times ``M / (colsum + eps)`` then ``M / (rowsum +
  eps)``; ``u = H_pre X``; ``y = F(RMSNorm(u))``; ``X' = H_res X +
  H_post y^T``. Streams start as ``n`` copies and end as a sum;
- latent attention with **the keys concatenated**, 192 wide, a masked
  softmax over each head's whole score matrix at the YaRN scale
  (``(dn + dr)^-1/2 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``),
  the rotation's frequencies YaRN's (the ramp between ``beta_fast`` and
  ``beta_slow`` turns in the original length, written out here) and its
  amplitude ``m(mscale) / m(mscale_all_dim)``;
- the routed layer (**every held expert on every token**), the dense
  SwiGLU, the loss's tail, the MTP module's projection and the first
  AdamW step are ``references/joyai.py``'s own functions: the stack is
  that one's; the MTP module runs over the summed stream, its block
  under the same residual path.

As ``references/joyai.py`` it runs on the chip after the window beside
the live train state, so the gradient is taken **a block at a time**
(the forward pass keeps each block's input state, 235 MB at 4,096
tokens and 4 x 3,584 lanes in float32), heads and experts one at a time
under ``jax.checkpoint``; the parameters may wait on the host (numpy)
and come to the device a block at a time.

Returns ``loss``, ``lm_loss``, ``mtp_loss``, ``grad_norm``,
``moe_absent_route_share``, and the mechanism's own keys
``hc_stream_spread`` (the RMS of ``X_L[i] - mean_i X_L[i]`` over the RMS
of ``X_L``) and, given ``spec["grad_groups"]`` (the configuration's:
``grad_norm_hc``), the norm over the maps' leaves alone (``phi``, ``b``,
``alpha`` of every sub-layer); with ``spec["adamw"]``
``update_norm``, the norm of the optimizer's first step written out
(clip, AdamW from zero moments, float32). ``spec["operand_dtype"]``
gives the low reading: every matmul operand that the program holds in
its compute type rounded to that type first (the router's matmul left
in float32, as the program leaves it; the maps' product rounded, as the
program rounds it).
"""

from __future__ import annotations

import functools
import math
import re

SINKHORN_NORM_EPS = 1e-6        # the maps' own norm, beside hc_eps


@functools.lru_cache(maxsize=1)
def _joyai():
    """``references/joyai.py``: the parts of the stack that are the same
    (the RMSNorm, SwiGLU, the sigmoid-routed layer with every held
    expert on every token, the loss's tail, the MTP module's projection,
    the first AdamW step)."""
    from benchlib import manifest
    return manifest.load_reference("joyai")


def _rounder(dtype):
    return _joyai()._rounder(dtype)


def _rms_norm(x, scale, eps):
    return _joyai()._rms_norm(x, scale, eps)


def _yarn(spec: dict):
    """(inverse frequencies [dr / 2], the amplitude on cos and sin, the
    factor on the softmax scale) of the rotation; plain RoPE without a
    ``rope_scaling`` group."""
    import jax.numpy as jnp
    dr, theta = spec["rope_dim"], spec["rope_theta"]
    f = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    y = spec.get("rope_scaling")
    if not y:
        return f, 1.0, 1.0

    def pair_that_turns(n):     # in original_len positions
        return (dr * math.log(y["original_len"] / (2 * math.pi * n))
                / (2 * math.log(theta)))
    low = max(math.floor(pair_that_turns(y["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(y["beta_slow"])), dr - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv = f / y["factor"] * ramp + f * (1.0 - ramp)

    def m(s):
        return 0.1 * s * math.log(y["factor"]) + 1.0
    return (inv, m(y["mscale"]) / m(y["mscale_all_dim"]),
            m(y["mscale_all_dim"]) ** 2)


def _rope(x, inv, amplitude):
    """Rotate the pairs (2i, 2i + 1) of the last axis of ``x`` [rows,
    seq, ..., dr] by position x ``inv[i]``."""
    import jax.numpy as jnp
    t, dr = x.shape[1], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # [seq, dr/2]
    ang = ang.reshape(1, t, *([1] * (x.ndim - 3)), dr // 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    return amplitude * jnp.stack(
        [even * jnp.cos(ang) - odd * jnp.sin(ang),
         even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1).reshape(x.shape)


def _attention(p, h, spec, rnd):
    import jax
    import jax.numpy as jnp

    rows, t, _ = h.shape
    heads, dn, dr = spec["n_head"], spec["nope_dim"], spec["rope_dim"]
    eps = spec["rms_eps"]
    inv, amplitude, score_factor = _yarn(spec)
    scale = score_factor / math.sqrt(dn + dr)
    h = rnd(h)
    c_q = rnd(_rms_norm(h @ rnd(p["q_down"]["proj"]["kernel"]),
                        p["q_down"]["norm"]["scale"], eps))
    kv = h @ rnd(p["kv_down"]["proj"]["kernel"])
    c_kv = rnd(_rms_norm(kv[..., :spec["kv_rank"]],
                         p["kv_down"]["norm"]["scale"], eps))
    k_r = _rope(kv[..., spec["kv_rank"]:], inv, amplitude)
    q = jnp.concatenate([
        (c_q @ rnd(p["q_up"]["nope"])).reshape(rows, t, heads, dn),
        _rope((c_q @ rnd(p["q_up"]["rope"])).reshape(rows, t, heads, dr),
              inv, amplitude)], -1)
    k = jnp.concatenate([
        (c_kv @ rnd(p["kv_up"]["k"])).reshape(rows, t, heads, dn),
        jnp.broadcast_to(k_r[:, :, None], (rows, t, heads, dr))], -1)
    v = (c_kv @ rnd(p["kv_up"]["v"])).reshape(rows, t, heads, -1)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):
        q, k, v = qkv                               # [rows, seq, width]
        s = jnp.einsum("btd,bsd->bts", rnd(q), rnd(k)) * scale
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", rnd(w), rnd(v))

    y = jax.lax.map(head, tuple(jnp.moveaxis(z, 2, 0) for z in (q, k, v)))
    y = jnp.moveaxis(y, 0, 2).reshape(rows, t, -1)
    return rnd(y) @ rnd(p["out_proj"]["kernel"])


def residual_maps(p, x, spec, rnd=lambda z: z):
    """``(H_pre [rows, seq, n], H_post [rows, seq, n], H_res [rows, seq,
    n, n])`` of one sub-layer from the state ``x`` [rows, seq, n, d] and
    its maps ``p`` = {phi, b, alpha}."""
    import jax
    import jax.numpy as jnp

    n = spec["hc_mult"]
    flat = x.reshape(*x.shape[:2], -1)
    r = 1.0 / jnp.sqrt((flat * flat).mean(-1, keepdims=True)
                       + SINKHORN_NORM_EPS)
    m = (rnd(flat) @ rnd(p["phi"])) * r
    b, alpha = p["b"], p["alpha"]
    h_pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + b[n:2 * n])
    clamp = spec["hc_res_clamp"]
    a = jnp.clip(alpha[2] * m[..., 2 * n:] + b[2 * n:], -clamp, clamp)
    mat = jnp.exp(a.reshape(*a.shape[:2], n, n))    # [.., i, j], rows first
    for _ in range(spec["hc_sinkhorn_iters"]):
        mat = mat / (mat.sum(-2, keepdims=True) + spec["hc_eps"])   # columns
        mat = mat / (mat.sum(-1, keepdims=True) + spec["hc_eps"])   # rows
    return h_pre, h_post, mat


def _around(p_maps, f, x, spec, rnd):
    """One sub-layer ``f`` (its norm inside) round the state ``x``."""
    import jax.numpy as jnp
    if spec["hc_mult"] == 1:
        return x + f(x)
    h_pre, h_post, h_res = residual_maps(p_maps, x, spec, rnd)
    u = jnp.einsum("bti,btid->btd", h_pre, x)
    y = f(u)
    return (jnp.einsum("btij,btjd->btid", h_res, x)
            + h_post[..., None] * y[:, :, None])


def _block(routed: bool, spec: dict):
    """(p, x) -> (the block's output, the routes per expert or None);
    ``x`` is ``[rows, seq, n, d]`` at ``hc_mult`` > 1, else ``[rows,
    seq, d]``."""
    rnd = _rounder(spec.get("operand_dtype"))
    eps = spec["rms_eps"]

    def block(p, x):
        x = _around(
            p.get("hc_attn"),
            lambda u: _attention(p["attn"], _rms_norm(
                u, p["attn_norm"]["scale"], eps), spec, rnd), x, spec, rnd)
        loads = []

        def mlp(u):
            h = _rms_norm(u, p["mlp_norm"]["scale"], eps)
            if not routed:
                return _joyai()._swiglu(p["mlp"], rnd(h), rnd)
            y, load = _joyai()._moe(p["mlp"], h, spec, rnd)
            loads.append(load)
            return y
        x = _around(p.get("hc_mlp"), mlp, x, spec, rnd)
        return x, (loads[0] if loads else None)
    return block


def _expand(x, spec):
    import jax.numpy as jnp
    n = spec["hc_mult"]
    return x if n == 1 else jnp.repeat(x[:, :, None], n, axis=2)


def _collapse(x, spec):
    return x if spec["hc_mult"] == 1 else x.sum(2)


def stream_spread(x):
    """The RMS of ``X[i] - mean_i X[i]`` over the RMS of ``X``,
    ``x`` [rows, seq, n, d]."""
    import jax.numpy as jnp
    off = x - x.mean(2, keepdims=True)
    return jnp.sqrt((off * off).sum() / (x * x).sum())


def _f32(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def logits(params, tokens, next_tokens, spec: dict):
    """(main logits, the MTP module's or None), [rows, seq, vocab]:
    the whole forward pass in one piece, for tests at small sizes."""
    import jax

    params = _f32(params)
    eps = spec["rms_eps"]
    with jax.default_matmul_precision("highest"):
        emb, head = params["wte"]["embedding"], params["lm_head"]["kernel"]
        x = _expand(emb[tokens], spec)
        for i in range(spec["n_layer"]):
            x, _ = _block(i >= spec["dense_layers"], spec)(params[f"h_{i}"], x)
        x = _collapse(x, spec)
        main = _rms_norm(x, params["norm_f"]["scale"], eps) @ head
        if not spec["mtp_depth"]:
            return main, None
        u = _joyai()._mtp_proj(spec)(params["mtp"], emb[next_tokens], x)
        u, _ = _block(True, spec)(params["mtp"]["h"], _expand(u, spec))
        return main, _rms_norm(_collapse(u, spec),
                               params["mtp_norm"]["scale"], eps) @ head


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the numbers, the gradient tree or None) of the whole batch at
    ``params``, float32 throughout. ``batch`` is {"tokens", "targets"},
    [rows, seq]. ``spec``: n_layer, dense_layers, mtp_depth, mtp_weight,
    n_head, kv_rank, nope_dim, rope_dim, rope_theta, rope_scaling
    (factor, original_len, beta_fast, beta_slow, mscale, mscale_all_dim;
    or None), top_k, norm_topk_prob, route_scale, experts_held (first,
    count), rms_eps, hc_mult, hc_sinkhorn_iters, hc_eps, hc_res_clamp,
    for the low reading operand_dtype, and ``grad_groups`` {name: regular
    expression over a gradient leaf's path, ``h_1/hc_attn/phi``}: the
    norm of the leaves each finds is among the numbers under its name
    (the cell's ``grad_norm_hc``). ``params`` may be numpy's, on
    the host: a top-level entry at a time is on the device. Without
    ``keep_grads`` a block's gradient lives only until its squared norms
    are taken; the kept tree is numpy's."""
    import jax
    import jax.numpy as jnp

    tokens, targets = batch["tokens"], batch["targets"]
    kinds = [i >= spec["dense_layers"] for i in range(spec["n_layer"])]
    forward = {k: jax.jit(_block(k, spec)) for k in set(kinds) | {True}}

    def pull(routed):
        @jax.jit
        def back(p, x, dy):
            return jax.vjp(lambda p, x: _block(routed, spec)(p, x)[0],
                           p, x)[1](dy)
        return back
    backward = {k: pull(k) for k in set(kinds) | {True}}

    grads, squares = {}, {}     # squares: a leaf's path -> its squared norm

    def took(name, g):
        for path, z in jax.tree_util.tree_flatten_with_path(g)[0]:
            squares["/".join([name, *(k.key for k in path)])] = float(
                jnp.sum(z * z))
        if keep_grads:
            grads[name] = jax.device_get(g)

    with jax.default_matmul_precision("highest"):
        emb = _f32(params["wte"]["embedding"])
        head = _f32(params["lm_head"]["kernel"])
        x = _expand(emb[tokens], spec)
        inputs, loads = [], []
        for i, routed in enumerate(kinds):
            inputs.append(x)
            x, load = forward[routed](_f32(params[f"h_{i}"]), x)
            if load is not None:
                loads.append(load)
        out = {}
        if spec["hc_mult"] > 1:
            out["hc_stream_spread"] = float(stream_spread(x))
        x = _collapse(x, spec)
        lm, (g_norm, g_head, dx) = jax.jit(jax.value_and_grad(
            _joyai()._tail(spec, False), argnums=(0, 1, 2)))(
                _f32(params["norm_f"]["scale"]), head, x, targets)
        took("norm_f", {"scale": g_norm})
        loss, out = lm, {"lm_loss": float(lm), **out}
        d_emb = jnp.zeros_like(emb)
        if spec["mtp_depth"]:
            weight = spec["mtp_weight"]
            mtp = _f32(params["mtp"])
            proj_in = ({k: mtp[k] for k in ("enorm", "hnorm", "eh_proj")},
                       emb[targets], x)
            u, pull_proj = jax.vjp(jax.jit(_joyai()._mtp_proj(spec)), *proj_in)
            u = _expand(u, spec)
            u_out, load = forward[True](mtp["h"], u)
            loads.append(load)
            second = jnp.roll(targets, -1, 1)   # a row's last one unused
            mtp_loss, (g_norm, g_head2, du) = jax.jit(jax.value_and_grad(
                _joyai()._tail(spec, True), argnums=(0, 1, 2)))(
                    _f32(params["mtp_norm"]["scale"]), head,
                    _collapse(u_out, spec), second)
            took("mtp_norm", {"scale": weight * g_norm})
            g_head = g_head + weight * g_head2
            g_block, du = backward[True](mtp["h"], u,
                                         weight * _expand(du, spec))
            g_proj, d_e, dx_mtp = pull_proj(_collapse(du, spec))
            took("mtp", {**g_proj, "h": g_block})
            del mtp, g_block
            dx = dx + dx_mtp
            d_emb = d_emb.at[targets].add(d_e)
            loss = lm + weight * mtp_loss
            out["mtp_loss"] = float(mtp_loss)
        took("lm_head", {"kernel": g_head})
        dx = _expand(dx, spec)      # the sum's cotangent, to every stream
        for i in reversed(range(len(kinds))):
            g, dx = backward[kinds[i]](_f32(params[f"h_{i}"]),
                                       inputs.pop(), dx)
            took(f"h_{i}", g)
        took("wte", {"embedding": d_emb.at[tokens].add(_collapse(dx, spec))})
    out = {"loss": float(loss), **out,
           "grad_norm": math.sqrt(sum(squares.values()))}
    for name, pattern in spec.get("grad_groups", {}).items():
        out[name] = math.sqrt(sum(
            sq for path, sq in squares.items() if re.search(pattern, path)))
    if loads:
        first, held = spec["experts_held"]
        load = jnp.stack(loads)
        out["moe_absent_route_share"] = 1.0 - float(
            load[:, first:first + held].sum() / load.sum())
    return out, (grads if keep_grads else None)


def loss_and_grad_norm(params, batch, spec: dict) -> dict:
    """{"loss", "lm_loss", "mtp_loss", "grad_norm",
    "moe_absent_route_share", "hc_stream_spread"}, a key a group of
    ``spec["grad_groups"]`` and, given ``spec["adamw"]``,
    ``"update_norm"``: ``loop.py`` holds every key
    against the metric of that name of the program's first dispatch,
    all at the configuration's one ``rtol``."""
    adamw = spec.get("adamw")
    out, grads = loss_and_grads(params, batch, spec, keep_grads=bool(adamw))
    if adamw:
        out["update_norm"] = _joyai().adamw_first_change(
            params, grads, out["grad_norm"], adamw)
    return out

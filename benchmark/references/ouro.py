"""Ouro-2.6B (a looped language model: one stack of layers run ``R``
times with one set of weights, an exit gate and a head after every pass)
as plain ``jax.numpy`` in float32: the configuration's plain reference.
It shares no code with ``ray_tpu/``: it reads the program's parameter
tree and the same batch and computes the model the straightforward way,
from the layer equations (``configs/ouro-2.6b.json`` repeats them):

- ``h_0 = E[x]``; pass ``t = 1..R`` is **a Python loop over the same
  ``L`` parameter sets**: ``u = h_{t-1}``; a layer: ``a = RMSNorm_1(u)``;
  ``q, k, v = W_q a, W_k a, W_v a``; RoPE on every lane of q and k in
  halves (lane ``i`` with lane ``i + D/2``), **written out from position
  numbers** at ``theta^(-2i/D)``; attention as **a masked softmax a head
  and a block of query rows at a time**, the mask from the definition
  (key ``j`` is seen by row ``t`` where ``j <= t``); ``u = u +
  RMSNorm_2(W_o o)``; ``m = RMSNorm_3(u)``; ``u = u + RMSNorm_4(W_d
  (SiLU(W_g m) * W_u m))``;
- ``h_t = RMSNorm_f(u)`` closes every pass and is what the next starts
  from;
- after every pass the logits ``W_head h_t`` and ``l_t = -log
  softmax(z_t)[y]``, **a chunk of rows at a time**, and the gate
  ``lambda_t = sigmoid(w_g . h_t + b_g)``;
- the exit distribution from products, as written: ``S_0 = 1``, ``S_t =
  S_{t-1} (1 - lambda_t)``; ``p_t = lambda_t S_{t-1}``, ``t < R``;
  ``p_R = S_{R-1}``; ``H = -sum_t p_t log p_t``; ``loss = mean over
  tokens of (sum_t p_t l_t - beta H)``.

It runs on the chip after the window beside the live train state, so the
gradient is taken **a block application at a time** (the forward pass
keeps each application's input, 33.5 MB at 4,096 tokens in float32, 36
of them; the backward walks the passes from the last, each block's
``jax.vjp`` recomputing its forward, every head and row block under
``jax.checkpoint``) and **a shared leaf's gradient is the sum over the
passes**, added as the passes are walked. The parameters may wait on the
host (numpy) and come to the device a block at a time.

Returns ``loss``, ``grad_norm``, ``lm_loss_ut_1`` .. ``lm_loss_ut_R``
(the mean ``l_t`` a pass), ``exit_mean_step`` (the mean of ``sum_t t
p_t``), ``exit_entropy`` (the mean ``H``), a key a group of
``spec["grad_groups"]`` (the configuration's: ``grad_norm_blocks`` over
``^h_[0-9]+/``, every shared leaf, and ``grad_norm_head`` over
``^lm_head/``) and, with ``spec["adamw"]``, ``update_norm``
(``references/joyai.py::adamw_first_change``). ``spec["operand_dtype"]``
gives the low reading: every matmul operand that the program holds in its
compute type rounded to that type first (the gate's product is no matmul
and stays float32, as the program leaves it).
"""

from __future__ import annotations

import math
import re

ROW_BLOCK = 1024     # score rows, and rows of logits, computed at a time


def _other(name: str):
    from benchlib import manifest
    return manifest.load_reference(name)


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x [rows, seq, heads, D]: lane ``i`` and lane ``i + D/2`` rotated by
    position x ``theta^(-2i/D)``."""
    import jax.numpy as jnp
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _softmax_attention(q, k, v, rnd):
    """q [rows, seq, H, D] against k, v [rows, seq, G, D], head ``i`` on
    group ``i // (H / G)``: a masked softmax a head and a block of score
    rows at a time."""
    import jax
    import jax.numpy as jnp

    rows, t, heads, d = q.shape
    groups = k.shape[2]
    blk = min(t, ROW_BLOCK)
    at = jnp.arange(t)

    @jax.checkpoint
    def block(qb, kh, vh, start):
        s = jnp.einsum("btd,bsd->bts", rnd(qb), rnd(kh)) / math.sqrt(d)
        seen = at[None, :] <= (start + jnp.arange(blk))[:, None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", rnd(w), rnd(vh))

    def head(qkv):
        qh, kh, vh = qkv                                # [rows, seq, D]
        qb = jnp.moveaxis(qh.reshape(rows, t // blk, blk, d), 1, 0)
        out = jax.lax.map(lambda a: block(a[0], kh, vh, a[1]),
                          (qb, jnp.arange(t // blk) * blk))
        return jnp.moveaxis(out, 0, 1).reshape(rows, t, d)

    per = heads // groups
    kv = [jnp.repeat(jnp.moveaxis(z, 2, 0), per, axis=0) for z in (k, v)]
    out = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), *kv))
    return jnp.moveaxis(out, 0, 2)


def _block(spec: dict):
    """(p, u) -> u after one layer: the four-norm block."""
    import jax
    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))
    eps, d = spec["rms_eps"], spec["head_dim"]

    def block(p, u):
        rows, t, _ = u.shape
        a = rnd(_rms_norm(u, p["attn_norm"]["scale"], eps))
        att = p["attn"]
        q = (a @ rnd(att["q"]["kernel"])).reshape(rows, t, -1, d)
        k = (a @ rnd(att["k"]["kernel"])).reshape(rows, t, -1, d)
        v = (a @ rnd(att["v"]["kernel"])).reshape(rows, t, -1, d)
        o = _softmax_attention(_rope(q, spec["rope_theta"]),
                               _rope(k, spec["rope_theta"]), v, rnd)
        y = rnd(o.reshape(rows, t, -1)) @ rnd(att["out"]["kernel"])
        u = u + _rms_norm(y, p["attn_post_norm"]["scale"], eps)
        m = rnd(_rms_norm(u, p["mlp_norm"]["scale"], eps))
        mlp = p["mlp"]
        act = (jax.nn.silu(m @ rnd(mlp["gate"]["kernel"]))
               * (m @ rnd(mlp["up"]["kernel"])))
        y = rnd(act) @ rnd(mlp["down"]["kernel"])
        return u + _rms_norm(y, p["mlp_post_norm"]["scale"], eps)
    return block


def exit_distribution(lam):
    """``p`` [R, ...] from ``lambda`` [R, ...], by the products as
    written: ``p_t = lambda_t S_{t-1}``, the last pass the remainder."""
    import jax.numpy as jnp
    r = lam.shape[0]
    stay = jnp.ones_like(lam[0])
    p = []
    for t in range(r):
        p.append(stay if t == r - 1 else lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(p)


def _tail(spec: dict):
    """(head [d, V], the gate {kernel [d, 1], bias [1]}, hs [R, rows,
    seq, d], targets) -> (the loss, (the mean l_t a pass [R], the mean
    exit step, the mean entropy))."""
    import jax
    import jax.numpy as jnp
    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))

    def tail(head, gate, hs, targets):
        r, rows, t, d = hs.shape
        blk = min(t, ROW_BLOCK)
        w = rnd(head)

        @jax.checkpoint
        def chunk(h, y):                    # [rows, blk, d], [rows, blk]
            logp = jax.nn.log_softmax(rnd(h) @ w, axis=-1)
            return -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]

        def one_pass(h):
            hb = jnp.moveaxis(h.reshape(rows, t // blk, blk, d), 1, 0)
            yb = jnp.moveaxis(targets.reshape(rows, t // blk, blk), 1, 0)
            out = jax.lax.map(lambda a: chunk(*a), (hb, yb))
            return jnp.moveaxis(out, 0, 1).reshape(rows, t)

        nll = jnp.stack([one_pass(hs[i]) for i in range(r)])    # l_t
        lam = jax.nn.sigmoid((hs * gate["kernel"][:, 0]).sum(-1)
                             + gate["bias"][0])
        p = exit_distribution(lam)
        entropy = -(p * jnp.log(p)).sum(0)
        loss = ((p * nll).sum(0) - spec["exit_beta"] * entropy).mean()
        steps = jnp.arange(1, r + 1, dtype=jnp.float32)[:, None, None]
        return loss, (nll.mean((1, 2)), (p * steps).sum(0).mean(),
                      entropy.mean())
    return tail


def _f32(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def forward(params, tokens, spec: dict):
    """(every pass's logits [R, rows, seq, V], the gate's lambda [R, rows,
    seq]): the whole forward pass in one piece, for tests at small
    sizes."""
    import jax
    import jax.numpy as jnp

    params = _f32(params)
    block = _block(spec)
    with jax.default_matmul_precision("highest"):
        h = params["wte"]["embedding"][tokens]
        hs = []
        for _ in range(spec["ut_steps"]):
            for i in range(spec["n_layer"]):
                h = block(params[f"h_{i}"], h)
            h = _rms_norm(h, params["norm_f"]["scale"], spec["rms_eps"])
            hs.append(h)
        hs = jnp.stack(hs)
        gate = params["exit_gate"]
        lam = jax.nn.sigmoid((hs * gate["kernel"][:, 0]).sum(-1)
                             + gate["bias"][0])
        return hs @ params["lm_head"]["kernel"], lam


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the numbers, the gradient tree or None) of the whole batch at
    ``params``, float32 throughout. ``batch`` is {"tokens", "targets"},
    [rows, seq]. ``spec``: n_layer, ut_steps, head_dim, rope_theta,
    rms_eps, exit_beta, for the low reading operand_dtype, and
    ``grad_groups`` {name: regular expression over a gradient leaf's
    path, ``h_1/attn/q/kernel``}: the norm of the leaves each finds is
    among the numbers under its name. ``params`` may be numpy's, on the
    host: a block's are on the device while it runs. The kept tree is
    numpy's, on the host."""
    import jax
    import jax.numpy as jnp

    tokens, targets = batch["tokens"], batch["targets"]
    layers, passes = spec["n_layer"], spec["ut_steps"]
    eps = spec["rms_eps"]
    forward_block = jax.jit(_block(spec))

    @jax.jit
    def backward_block(p, u, du):
        return jax.vjp(_block(spec), p, u)[1](du)

    final_norm = jax.jit(lambda u, scale: _rms_norm(u, scale, eps))

    @jax.jit
    def backward_norm(scale, u, dh):
        return jax.vjp(lambda s, u: _rms_norm(u, s, eps), scale, u)[1](dh)

    sums: dict = {}      # a top-level entry -> its gradient, summed

    def add(name, g):
        sums[name] = (jax.tree_util.tree_map(jnp.add, sums[name], g)
                      if name in sums else g)

    with jax.default_matmul_precision("highest"):
        emb = _f32(params["wte"]["embedding"])
        scale_f = _f32(params["norm_f"]["scale"])
        h = emb[tokens]
        inputs, hs = [], []
        for _ in range(passes):
            for i in range(layers):
                inputs.append(h)
                h = forward_block(_f32(params[f"h_{i}"]), h)
            inputs.append(h)
            h = final_norm(h, scale_f)
            hs.append(h)
        (loss, (per_pass, mean_step, entropy)), (g_head, g_gate, d_hs) = (
            jax.jit(jax.value_and_grad(_tail(spec), argnums=(0, 1, 2),
                                       has_aux=True))(
                _f32(params["lm_head"]["kernel"]),
                _f32(params["exit_gate"]), jnp.stack(hs), targets))
        del hs
        add("lm_head", {"kernel": g_head})
        add("exit_gate", g_gate)
        del g_head
        dh = jnp.zeros_like(h)
        for at in reversed(range(passes)):
            dh = dh + d_hs[at]
            g_scale, du = backward_norm(scale_f, inputs.pop(), dh)
            add("norm_f", {"scale": g_scale})
            for i in reversed(range(layers)):
                g, du = backward_block(_f32(params[f"h_{i}"]),
                                       inputs.pop(), du)
                add(f"h_{i}", g)
                del g
            dh = du         # the cotangent of h_{t-1}
        add("wte", {"embedding": jnp.zeros_like(emb).at[tokens].add(dh)})
    squares = {}        # a leaf's path -> its squared norm
    for name, g in sums.items():
        for path, z in jax.tree_util.tree_flatten_with_path(g)[0]:
            squares["/".join([name, *(k.key for k in path)])] = float(
                jnp.sum(z * z))
    out = {"loss": float(loss),
           "grad_norm": math.sqrt(sum(squares.values())),
           **{f"lm_loss_ut_{i + 1}": float(x)
              for i, x in enumerate(per_pass)},
           "exit_mean_step": float(mean_step),
           "exit_entropy": float(entropy)}
    for name, pattern in spec.get("grad_groups", {}).items():
        out[name] = math.sqrt(sum(
            sq for path, sq in squares.items() if re.search(pattern, path)))
    return out, (jax.device_get(sums) if keep_grads else None)


def loss_and_grad_norm(params, batch, spec: dict) -> dict:
    """{"loss", "grad_norm", "lm_loss_ut_1" .. "lm_loss_ut_R",
    "exit_mean_step", "exit_entropy"}, a key a group of
    ``spec["grad_groups"]`` and, given ``spec["adamw"]``,
    ``"update_norm"``: ``loop.py`` holds every key against the metric of
    that name of the program's first dispatch, all at the configuration's
    one ``rtol``."""
    adamw = spec.get("adamw")
    out, grads = loss_and_grads(params, batch, spec, keep_grads=bool(adamw))
    if adamw:
        out["update_norm"] = _other("joyai").adamw_first_change(
            params, grads, out["grad_norm"], adamw)
    return out

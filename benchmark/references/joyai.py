"""JoyAI-LLM-Flash (the DeepSeek-V3 architecture) as plain ``jax.numpy``
in float32: the configuration's plain reference. It shares no code with
``ray_tpu/``: it reads the program's parameter tree and the same batch
and computes the model the straightforward way, from the layer
equations (``configs/joyai-llm-flash.json`` repeats them):

- latent attention with **the keys concatenated**: head ``i``'s query is
  ``[q_nope_i | rope(q_rope_i)]`` and its key ``[k_nope_i | rope(k_r)]``,
  192 wide, the rotated shared key copied to every head; a masked softmax
  over the head's whole score matrix; values 128 wide. No kernel, no
  second score matmul, no log-sum-exp bookkeeping;
- a routed layer with **every held expert on every token**, times the
  token's router weight for that expert or zero: no sort, no groups. The
  router is the sigmoid one: the top-k of ``s + b``, weights ``s``
  without ``b`` over their sum, times the scale. Given the same share of
  the experts as the program (``spec["experts_held"]``), it leaves out
  what the absent experts would add, as the program does; the shared
  expert and the dense layer are plain SwiGLU;
- the multi-token-prediction module as arXiv:2412.19437 section 2.2
  writes it: ``W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_L[i])]``, one more
  block, a norm, the main head; its loss the mean over the ``T - 1``
  positions of a row that have a second-next token.

Departures from the published description, both in how the weights are
laid out and not in what is computed: the program holds ``W_qb`` and
``W_kvb`` with their columns grouped by part (all heads' 128-wide parts,
then all heads' rotary parts: ``q_up/nope``, ``q_up/rope``, ``kv_up/k``,
``kv_up/v``), a permutation of the published columns; and the rotation
is over interleaved pairs ``(2i, 2i + 1)`` as ``rope_interleave`` says
the checkpoint holds them, without the published code's re-ordering to
halves first, which permutes a query's and a key's rotary lanes alike
and leaves every score as it was.

It runs on the chip after the window, beside the live train state and
the kept initial parameters, so it is frugal with memory and not with
time: the gradient is taken **a layer at a time** (the forward pass keeps
each block's input, 67 MB at 8,192 tokens; each block is differentiated
alone from the cotangent of its output, its gradient's squared norm
taken and the gradient dropped), and inside a block the heads and the
experts are walked one at a time under ``jax.checkpoint``. The
embedding's and the head's gradients, which two paths reach, are summed
before their norm is taken.

``spec["adamw"]`` (the configuration's ``optimizer`` group; a run of the
benchmark gives it) adds **the optimizer's first step**, written out:
the gradient clipped to its global norm, AdamW from zero moments in
float32, and the norm of what that changes in the parameters,
``update_norm``. The cell holds the program's own first step against it
(``builders/joyai.py``): a state left unchanged reads 0 there, a rate
off by a tenth reads a tenth off. The gradient waits for its norm on the
host meanwhile, a block at a time, so nothing more lives on the device.

``spec["operand_dtype"]`` (absent in a run of the benchmark) gives the
reading that the configuration's limit is set against from below: the
same computation with every matmul operand that the program holds in its
compute type rounded to that type first (``references/olmoe.py``'s
``_rounder``), the router's matmul left in float32 as the program leaves
it. ``tools/limit.py`` takes both readings.
"""

from __future__ import annotations

import math


def _rounder(dtype):
    from benchlib import manifest
    return manifest.load_reference("olmoe")._rounder(dtype)


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate the pairs (2i, 2i + 1) of the last axis of ``x`` [rows,
    seq, ..., dr] by position x theta^(-2i / dr)."""
    import jax.numpy as jnp
    t, dr = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv     # [seq, dr/2]
    ang = ang.reshape(1, t, *([1] * (x.ndim - 3)), dr // 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      even * jnp.sin(ang) + odd * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def _swiglu(p, h, rnd):
    import jax
    a = jax.nn.silu(h @ rnd(p["gate"]["kernel"])) * (h @ rnd(p["up"]["kernel"]))
    return rnd(a) @ rnd(p["down"]["kernel"])


def _attention(p, h, spec, rnd):
    import jax
    import jax.numpy as jnp

    rows, t, _ = h.shape
    heads, dn, dr = spec["n_head"], spec["nope_dim"], spec["rope_dim"]
    eps = spec["rms_eps"]
    h = rnd(h)
    c_q = rnd(_rms_norm(h @ rnd(p["q_down"]["proj"]["kernel"]),
                        p["q_down"]["norm"]["scale"], eps))
    kv = h @ rnd(p["kv_down"]["proj"]["kernel"])
    c_kv = rnd(_rms_norm(kv[..., :spec["kv_rank"]],
                         p["kv_down"]["norm"]["scale"], eps))
    k_r = _rope(kv[..., spec["kv_rank"]:], spec["rope_theta"])
    q = jnp.concatenate([
        (c_q @ rnd(p["q_up"]["nope"])).reshape(rows, t, heads, dn),
        _rope((c_q @ rnd(p["q_up"]["rope"])).reshape(rows, t, heads, dr),
              spec["rope_theta"])], -1)
    k = jnp.concatenate([
        (c_kv @ rnd(p["kv_up"]["k"])).reshape(rows, t, heads, dn),
        jnp.broadcast_to(k_r[:, :, None], (rows, t, heads, dr))], -1)
    v = (c_kv @ rnd(p["kv_up"]["v"])).reshape(rows, t, heads, -1)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):
        q, k, v = qkv                               # [rows, seq, width]
        s = jnp.einsum("btd,bsd->bts", rnd(q), rnd(k)) / math.sqrt(dn + dr)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", rnd(w), rnd(v))

    y = jax.lax.map(head, tuple(jnp.moveaxis(z, 2, 0) for z in (q, k, v)))
    y = jnp.moveaxis(y, 0, 2).reshape(rows, t, -1)
    return rnd(y) @ rnd(p["out_proj"]["kernel"])


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
        gate, up, down, weight = expert
        a = jax.nn.silu(hr @ rnd(gate)) * (hr @ rnd(up))
        return (rnd(a) @ rnd(down)) * weight[..., None]

    ex = p["experts"]
    y = jax.lax.map(one, (ex["gate_proj"], ex["up_proj"], ex["down_proj"],
                          mix)).sum(0)
    return y + _swiglu(p["shared"], hr, rnd), load


def _block(routed: bool, spec: dict):
    """(p, x) -> (the block's output, the routes per expert or None)."""
    rnd = _rounder(spec.get("operand_dtype"))
    eps = spec["rms_eps"]

    def block(p, x):
        x = x + _attention(p["attn"], _rms_norm(
            x, p["attn_norm"]["scale"], eps), spec, rnd)
        h = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        if not routed:
            return x + _swiglu(p["mlp"], rnd(h), rnd), None
        y, load = _moe(p["mlp"], h, spec, rnd)
        return x + y, load
    return block


def _tail(spec: dict, skip_last: bool):
    """(a final norm's scale, the head, x, targets) -> the mean
    cross-entropy; ``skip_last`` leaves each row's last position out
    (the MTP module's: ``targets`` are then the second-next tokens, the
    last of a row meaningless)."""
    rnd = _rounder(spec.get("operand_dtype"))

    def tail(scale, head, x, targets):
        import jax
        import jax.numpy as jnp
        h = rnd(_rms_norm(x, scale, spec["rms_eps"]))
        logp = jax.nn.log_softmax(h @ rnd(head), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return (nll[:, :-1] if skip_last else nll).mean()
    return tail


def _mtp_proj(spec: dict):
    """(the module's two norms and W_eh, the next tokens' embeddings,
    x_L) -> u."""
    rnd = _rounder(spec.get("operand_dtype"))
    eps = spec["rms_eps"]

    def proj(p, e, x):
        import jax.numpy as jnp
        both = jnp.concatenate([_rms_norm(e, p["enorm"]["scale"], eps),
                                _rms_norm(x, p["hnorm"]["scale"], eps)], -1)
        return rnd(both) @ rnd(p["eh_proj"]["kernel"])
    return proj


def logits(params, tokens, next_tokens, spec: dict):
    """(main logits, the MTP module's or None), [rows, seq, vocab]:
    the whole forward pass in one piece, for tests at small sizes."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    eps = spec["rms_eps"]
    with jax.default_matmul_precision("highest"):
        emb, head = params["wte"]["embedding"], params["lm_head"]["kernel"]
        x = emb[tokens]
        for i in range(spec["n_layer"]):
            x, _ = _block(i >= spec["dense_layers"], spec)(params[f"h_{i}"], x)
        main = _rms_norm(x, params["norm_f"]["scale"], eps) @ head
        if not spec["mtp_depth"]:
            return main, None
        u = _mtp_proj(spec)(params["mtp"], emb[next_tokens], x)
        u, _ = _block(True, spec)(params["mtp"]["h"], u)
        return main, _rms_norm(u, params["mtp_norm"]["scale"], eps) @ head


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the numbers, the gradient tree or None) of the whole batch at
    ``params``, float32 throughout. ``batch`` is {"tokens", "targets"},
    [rows, seq]. ``spec``: n_layer, dense_layers, mtp_depth, mtp_weight,
    n_head, kv_rank, nope_dim, rope_dim, rope_theta, top_k,
    norm_topk_prob, route_scale, experts_held (first, count), rms_eps,
    and for the low reading operand_dtype. Without ``keep_grads`` a
    block's gradient lives only until its squared norm is taken; the
    kept tree is numpy's, on the host, so the device holds a block's
    gradient no longer either way."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
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

    def sq(tree):
        return sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(tree))

    grads, total = {}, 0.0

    def took(name, g):
        nonlocal total
        total += float(sq(g))
        if keep_grads:
            grads[name] = jax.device_get(g)

    with jax.default_matmul_precision("highest"):
        emb, head = params["wte"]["embedding"], params["lm_head"]["kernel"]
        x = emb[tokens]
        inputs, loads = [], []
        for i, routed in enumerate(kinds):
            inputs.append(x)
            x, load = forward[routed](params[f"h_{i}"], x)
            if load is not None:
                loads.append(load)
        lm, (g_norm, g_head, dx) = jax.jit(jax.value_and_grad(
            _tail(spec, False), argnums=(0, 1, 2)))(
                params["norm_f"]["scale"], head, x, targets)
        took("norm_f", {"scale": g_norm})
        loss, out = lm, {"lm_loss": float(lm)}
        d_emb = jnp.zeros_like(emb)
        if spec["mtp_depth"]:
            weight = spec["mtp_weight"]
            mtp = params["mtp"]
            proj_in = ({k: mtp[k] for k in ("enorm", "hnorm", "eh_proj")},
                       emb[targets], x)
            u, pull_proj = jax.vjp(jax.jit(_mtp_proj(spec)), *proj_in)
            u_out, load = forward[True](mtp["h"], u)
            loads.append(load)
            second = jnp.roll(targets, -1, 1)   # a row's last one unused
            mtp_loss, (g_norm, g_head2, du) = jax.jit(jax.value_and_grad(
                _tail(spec, True), argnums=(0, 1, 2)))(
                    params["mtp_norm"]["scale"], head, u_out, second)
            took("mtp_norm", {"scale": weight * g_norm})
            g_head = g_head + weight * g_head2
            g_block, du = backward[True](mtp["h"], u, weight * du)
            g_proj, d_e, dx_mtp = pull_proj(du)
            took("mtp", {**g_proj, "h": g_block})
            dx = dx + dx_mtp
            d_emb = d_emb.at[targets].add(d_e)
            loss = lm + weight * mtp_loss
            out["mtp_loss"] = float(mtp_loss)
        took("lm_head", {"kernel": g_head})
        for i in reversed(range(len(kinds))):
            g, dx = backward[kinds[i]](params[f"h_{i}"], inputs.pop(), dx)
            took(f"h_{i}", g)
        took("wte", {"embedding": d_emb.at[tokens].add(dx)})
    out = {"loss": float(loss), **out, "grad_norm": math.sqrt(total)}
    if loads:
        first, held = spec["experts_held"]
        load = jnp.stack(loads)
        out["moe_absent_route_share"] = 1.0 - float(
            load[:, first:first + held].sum() / load.sum())
    return out, (grads if keep_grads else None)


def adamw_first_change(params, grads, grad_norm: float, o: dict) -> float:
    """The norm of what the optimizer's first step changes in
    ``params``: ``g`` clipped to a global norm of ``clip_global_norm``,
    then AdamW from zero moments, ``m = (1 - b1) g``, ``v = (1 - b2)
    g^2``, both divided by their bias corrections, and ``-
    learning_rate (m / (sqrt(v) + eps) + weight_decay p)`` on every
    parameter (no mask), float32 throughout. ``grads`` may live on the
    host; a top-level entry at a time is on the device."""
    import jax
    import jax.numpy as jnp

    clip = min(1.0, o["clip_global_norm"] / grad_norm)
    b1, b2, eps = o["b1"], o["b2"], o["eps"]

    @jax.jit
    def sq_change(p, g):
        def one(p, g):
            g = g * clip
            m, v = (1 - b1) * g, (1 - b2) * g * g   # from zero moments
            m, v = m / (1 - b1), v / (1 - b2)       # step 1's corrections
            d = -o["learning_rate"] * (m / (jnp.sqrt(v) + eps)
                                       + o["weight_decay"] * p)
            return jnp.sum(d * d)
        return sum(jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(one, p, g)))

    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    return math.sqrt(sum(float(sq_change(f32[name], grads[name]))
                         for name in grads))


def loss_and_grad_norm(params, batch, spec: dict) -> dict:
    """{"loss", "lm_loss", "mtp_loss", "grad_norm",
    "moe_absent_route_share"} and, given ``spec["adamw"]``,
    ``"update_norm"``: ``loop.py`` holds every key against the metric
    of that name of the program's first dispatch, all at the
    configuration's one ``rtol``. The routing statistic is the share of
    routes that land on **absent** experts (15/16 at an even load), for
    the reason ``references/nemotron_h.py`` gives: a route flipped by a
    bf16 activation moves the held share sixteen times as far."""
    adamw = spec.get("adamw")
    out, grads = loss_and_grads(params, batch, spec, keep_grads=bool(adamw))
    if adamw:
        out["update_norm"] = adamw_first_change(
            params, grads, out["grad_norm"], adamw)
    return out

"""Phi-4-mini-flash (SambaY: Mamba-1 scans and windowed differential
attention in a self-decoder; gated memory units and cross attention that
read one earlier layer's scan and one's K and V in a cross-decoder;
LayerNorm, dense SwiGLU MLPs, a tied table) as plain ``jax.numpy`` in
float32: the configuration's plain reference. It shares no code with
``ray_tpu/``: it reads the program's parameter tree and the same batch
and computes the model the straightforward way, from the layer equations
(``configs/phi-4-mini-flash-reasoning.json`` repeats them):

- **Mamba-1 as the recurrence itself**, one token at a time over a
  ``[C, N]`` state: ``h <- exp(dt_t (x) A) * h + (dt_t * x_t) (x) B_t``;
  ``y_t = h C_t + D * x_t``. No chunk, no associative scan, no running
  product of decays: a fault in the program's chunked form cannot be in
  here too. Its gradients come from reverse mode through that scan;
  kept whole it would save a state a token (1.34 GB a layer at 4,096
  rows), so the scan is nested: an outer scan over blocks of
  ``TOKEN_BLOCK`` tokens whose body is a ``jax.checkpoint`` of an inner
  scan of *the same per-token update*. That is bookkeeping, not algebra;
- the convolution as **shifted sums** (``y_t = b + sum_j w_j x_{t - 3 +
  j}``) and a SiLU; ``dt = softplus(W_dt delta + b_dt)``;
- differential attention as **two masked softmaxes a pair**, a pair of
  query heads and a block of query rows at a time, with the pairing
  written out (query heads ``2j, 2j + 1`` are ``q1, q2``; key/value
  heads ``2i, 2i + 1`` are ``k1, k2`` and ``v1, v2``; pair ``j`` reads
  pair ``j // (P / G)``) and the mask from the row and key numbers
  (``key <= row``, under a window also ``key > row - window``); ``o =
  A1 [v1 | v2] - lambda A2 [v1 | v2]``; the pair's RMSNorm, its scale and
  ``1 - lambda_init``;
- ``G`` and ``X`` read ``carry["m"]`` and ``carry["k"], carry["v"]``:
  the tensors that layer ``M*`` and layer ``F`` put there **by name**;
  every block passes the carry on, so the gradient of a later layer
  reaches the layer that made the tensor;
- the tied head over the slice, the loss a chunk of rows at a time.

**Departures from the published description**: none in the mathematics.
What ``config.json`` does not fix (the Mamba sizes, which layer is of
which kind, the biases, the depth that ``lambda_init`` counts) is the
configuration file's ``assumed``; dropout is 0 as published.

It runs on the chip after the window, beside the live train state, so
it is frugal with memory and not with time: the gradient is taken **a
layer at a time** as ``references/joyai.py`` takes it (a block's
backward is its ``vjp`` given the cotangents of the stream and of the
carry), and the parameters may wait on the host (numpy): a block's are
on the device only while the block runs.

Beside the loss and the gradient's norm it returns ``mamba_out_rms``,
the root mean square of ``y`` over the Mamba layers, and a key a group of
``spec["grad_groups"]`` (``grad_norm_mamba_ssm``, ``grad_norm_attn_diff``,
``grad_norm_yoco_kv``). ``spec["adamw"]`` adds the optimizer's first step
(``references/joyai.py::adamw_first_change``) and ``update_norm``;
``spec["operand_dtype"]`` (absent in a run of the benchmark) gives the
reading that the configuration's limit is set against from below: every
matmul operand that the program holds in its compute type rounded to
that type first; the recurrence, ``dt``, ``lambda`` and the norms (which
the program runs in float32) left alone.
"""

from __future__ import annotations

import math
import re

ROW_BLOCK = 1024        # score rows, and rows of logits, computed at a time
TOKEN_BLOCK = 128       # tokens of one recomputed block of the recurrence


def _other(name: str):
    from benchlib import manifest
    return manifest.load_reference(name)


def kind_of(spec: dict, layer: int) -> str:
    """``M``, ``S``, ``F``, ``G`` or ``X`` for ``layer`` from 0, by the
    architecture's rule: even layers a Mamba-family mixer, odd layers
    attention; the first half the self-decoder; layer ``N/2`` the last
    scan (``M*``), ``N/2 + 1`` the full layer (``F``)."""
    half = spec["n_layer"] // 2
    if layer % 2 == 0:
        return "M" if layer <= half else "G"
    if layer < half:
        return "S"
    return "F" if layer == half + 1 else "X"


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _layer_norm(x, p, eps):
    import jax.numpy as jnp
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _conv_silu(x, w, b):
    """silu(y), ``y[t] = b + sum_j w[j] x[t - (K - 1) + j]``, zeros
    before the start: x [rows, seq, C], w [K, C], b [C]."""
    import jax
    import jax.numpy as jnp
    taps, t = w.shape[0], x.shape[1]
    y = b
    for j in range(taps):
        back = taps - 1 - j
        y = y + w[j] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
    return jax.nn.silu(y)


def recurrence(x, dt, A, B, C):
    """``h_t C_t`` [rows, seq, C] of the selective scan, token by token
    (without the skip). x, dt [rows, seq, C]; A [C, N]; B, C [rows, seq,
    N]; the state [rows, C, N] starts at zero."""
    import jax
    import jax.numpy as jnp

    rows, t, c = x.shape

    def token(h, row):
        x, dt, B, C = row                               # [rows, .]
        h = (jnp.exp(dt[..., None] * A) * h
             + (dt * x)[..., None] * B[:, None, :])
        return h, jnp.sum(h * C[:, None, :], -1)

    @jax.checkpoint
    def block(h, rows_):
        # unrolled: eight tokens a turn of the device's loop (bookkeeping)
        return jax.lax.scan(token, h, rows_, unroll=8)

    size = math.gcd(t, TOKEN_BLOCK)
    per_block = tuple(
        jnp.moveaxis(z, 1, 0).reshape(t // size, size, rows, z.shape[-1])
        for z in (x, dt, B, C))
    _, y = jax.lax.scan(block, jnp.zeros((rows, c, A.shape[1]), jnp.float32),
                        per_block)
    return jnp.moveaxis(y.reshape(t, rows, c), 0, 1)


def _mamba(p, h, spec, rnd):
    """(the mixer's output, the scan's output ``y`` before the gate)."""
    import jax
    import jax.numpy as jnp

    n, r = spec["ssm_state"], spec["dt_rank"]
    x, z = jnp.split(rnd(h) @ rnd(p["in_proj"]["kernel"]), 2, -1)
    x = _conv_silu(x, p["conv1d"]["kernel"], p["conv1d"]["bias"])
    dbc = rnd(x) @ rnd(p["x_proj"]["kernel"])
    delta, B, C = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
    dt = jax.nn.softplus(rnd(delta) @ rnd(p["dt_proj"]["kernel"])
                         + p["dt_proj"]["bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), B, C) + p["D"] * x
    return rnd(y * jax.nn.silu(z)) @ rnd(p["out_proj"]["kernel"]), y


def _gmu(p, h, memory, rnd):
    """``W_out (SiLU(W_in h) * m)``."""
    import jax
    a = rnd(h) @ rnd(p["in_proj"]["kernel"])
    return rnd(jax.nn.silu(a) * memory) @ rnd(p["out_proj"]["kernel"])


def _diff_attention(p, h, carry, spec, kind, lam_init, rnd):
    """(the layer's output, its own ``k``, ``v`` [rows, seq, 2G, D]; an
    ``X`` layer returns the ones it read)."""
    import jax
    import jax.numpy as jnp

    rows, t, _ = h.shape
    heads, kv_heads, d = spec["n_head"], spec["n_kv_head"], spec["head_dim"]
    q_w, kv_w = heads * d, kv_heads * d
    if kind == "X":
        q = rnd(h) @ rnd(p["q"]["kernel"]) + p["q"]["bias"]
        k, v = carry["k"], carry["v"]
    else:
        qkv = rnd(h) @ rnd(p["qkv"]["kernel"]) + p["qkv"]["bias"]
        q = qkv[..., :q_w]
        k = qkv[..., q_w:q_w + kv_w].reshape(rows, t, kv_heads, d)
        v = qkv[..., q_w + kv_w:].reshape(rows, t, kv_heads, d)
    pairs, kv_pairs = heads // 2, kv_heads // 2
    read = jnp.arange(pairs) // (pairs // kv_pairs)     # pair j reads this
    q = q.reshape(rows, t, pairs, 2, d)
    kp = k.reshape(rows, t, kv_pairs, 2, d)[:, :, read]
    vp = v.reshape(rows, t, kv_pairs, 2 * d)[:, :, read]    # [v1 | v2]
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init)
    window = spec["window"] if kind == "S" else None
    blk = math.gcd(t, ROW_BLOCK)
    keys = jnp.arange(t)

    @jax.checkpoint
    def block(qb, kj, vj, start):
        at = (start + jnp.arange(blk))[:, None]
        seen = keys[None, :] <= at
        if window is not None:
            seen &= keys[None, :] > at - window

        def weights(q_, k_):
            s = jnp.einsum("btd,bsd->bts", rnd(q_), rnd(k_)) / math.sqrt(d)
            return rnd(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1))
        a1 = weights(qb[:, :, 0], kj[:, :, 0])
        a2 = weights(qb[:, :, 1], kj[:, :, 1])
        vj = rnd(vj)
        return (jnp.einsum("bts,bsd->btd", a1, vj)
                - lam * jnp.einsum("bts,bsd->btd", a2, vj))

    def pair(qkv):
        qj, kj, vj = qkv            # [rows, seq, 2, D] x 2, [rows, seq, 2D]
        qb = jnp.moveaxis(qj.reshape(rows, t // blk, blk, 2, d), 1, 0)
        out = jax.lax.map(lambda a: block(a[0], kj, vj, a[1]),
                          (qb, jnp.arange(t // blk) * blk))
        return jnp.moveaxis(out, 0, 1).reshape(rows, t, 2 * d)

    o = jax.lax.map(pair, tuple(jnp.moveaxis(z, 2, 0) for z in (q, kp, vp)))
    o = jnp.moveaxis(o, 0, 2)                           # [rows, seq, P, 2D]
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + spec["ln_eps"])
    o = o * p["subln"] * (1.0 - lam_init)
    y = (rnd(o.reshape(rows, t, -1)) @ rnd(p["out"]["kernel"])
         + p["out"]["bias"])
    return y, k, v


def _block(spec: dict, layer: int):
    """(p, x, carry, lambda_init) -> (x, carry, the mean square of the
    scan's output or None). ``carry`` is a dict: ``m`` once layer ``M*``
    has run, ``k`` and ``v`` once layer ``F`` has; a block hands on what
    it got, plus what it makes."""
    import jax
    import jax.numpy as jnp

    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))
    eps = spec["ln_eps"]
    kind = kind_of(spec, layer)
    makes_memory = layer == spec["n_layer"] // 2

    def block(p, x, carry, lam_init):
        h = _layer_norm(x, p["ln_1"], eps)
        out_sq = None
        if kind == "M":
            mixed, y = _mamba(p["mamba"], h, spec, rnd)
            out_sq = jnp.mean(y * y)
            if makes_memory:
                carry = {**carry, "m": y}
        elif kind == "G":
            mixed = _gmu(p["gmu"], h, carry["m"], rnd)
        else:
            mixed, k, v = _diff_attention(p["attn"], h, carry, spec, kind,
                                          lam_init, rnd)
            if kind == "F":
                carry = {**carry, "k": k, "v": v}
        x = x + mixed
        h = rnd(_layer_norm(x, p["ln_2"], eps))
        g, u = jnp.split(h @ rnd(p["mlp"]["gate_up"]["kernel"]), 2, -1)
        x = x + rnd(jax.nn.silu(g) * u) @ rnd(p["mlp"]["down"]["kernel"])
        return x, carry, out_sq
    return block


def _tail(spec: dict):
    """(the final norm's parameters, the table [V, d], x, targets) -> the
    mean cross-entropy against the tied table, a chunk of rows at a
    time."""
    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))

    def tail(norm, table, x, targets):
        import jax
        import jax.numpy as jnp
        h = rnd(_layer_norm(x, norm, spec["ln_eps"]))
        h, tg = h.reshape(-1, h.shape[-1]), targets.reshape(-1)
        n = h.shape[0]
        rows = math.gcd(n, ROW_BLOCK)
        head = rnd(table).T

        @jax.checkpoint
        def chunk(part):
            hc, tc = part
            logp = jax.nn.log_softmax(hc @ head, axis=-1)
            return -jnp.take_along_axis(logp, tc[:, None], -1).sum()

        return jax.lax.map(chunk, (h.reshape(n // rows, rows, -1),
                                   tg.reshape(n // rows, rows))).sum() / n
    return tail


def forward(params, tokens, spec: dict):
    """(logits [rows, seq, vocab], the mean square of each Mamba layer's
    ``y``): the whole forward pass in one piece, for tests at small
    sizes."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        table = params["wte"]["embedding"]
        x, carry, out_sq = table[tokens], {}, []
        for i in range(spec["n_layer"]):
            x, carry, sq = _block(spec, i)(params[f"h_{i}"], x, carry,
                                           lambda_init(i))
            if sq is not None:
                out_sq.append(sq)
        logits = _layer_norm(x, params["ln_f"], spec["ln_eps"]) @ table.T
    return logits, jnp.stack(out_sq)


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the numbers, the gradient tree or None) of the whole batch at
    ``params``, float32 throughout. ``batch`` is {"tokens", "targets"},
    [rows, seq]. ``spec``: n_layer, n_head, n_kv_head, head_dim, window,
    ssm_state, dt_rank, ln_eps, for the low reading operand_dtype, and
    ``grad_groups`` {name: regular expression over a gradient leaf's
    path, ``h_0/mamba/A_log``}: the norm of the leaves each finds is
    among the numbers under its name. Without ``keep_grads`` a block's
    gradient lives only until its squared norm is taken; the kept tree
    is numpy's, on the host."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    tokens, targets = batch["tokens"], batch["targets"]
    kinds = {}      # layers of one kind share their two programs

    def programs(i):
        key = (kind_of(spec, i), i == spec["n_layer"] // 2)
        if key not in kinds:
            block = _block(spec, i)
            kinds[key] = (jax.jit(block), jax.jit(
                lambda p, x, carry, lam, dx, dcarry: jax.vjp(
                    lambda *a: block(*a, lam)[:2], p, x, carry)[1](
                        (dx, dcarry))))
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
        x, carry = table[tokens], {}
        inputs, out_sq = [], []
        for i in range(spec["n_layer"]):
            inputs.append((x, carry))
            x, carry, sq = programs(i)[0](params[f"h_{i}"], x, carry,
                                          lambda_init(i))
            if sq is not None:
                out_sq.append(float(sq))
        loss, (g_norm, g_head, dx) = jax.jit(jax.value_and_grad(
            _tail(spec), argnums=(0, 1, 2)))(
                params["ln_f"], table, x, targets)
        took("ln_f", g_norm)
        # what the layers after the makers of the carry send back to them
        dcarry = jax.tree_util.tree_map(jnp.zeros_like, carry)
        for i in reversed(range(spec["n_layer"])):
            x_in, carry_in = inputs.pop()
            # a block's carry is what the block before it handed on, so
            # the cotangent that comes back is that block's
            g, dx, dcarry = programs(i)[1](
                params[f"h_{i}"], x_in, carry_in, lambda_init(i), dx, dcarry)
            took(f"h_{i}", g)
        # the tied table: the head's gradient and the embedding's
        took("wte", {"embedding": g_head.at[tokens].add(dx)})
    out = {"loss": float(loss),
           "grad_norm": math.sqrt(sum(squares.values())),
           "mamba_out_rms": math.sqrt(sum(out_sq) / len(out_sq))}
    for name, pattern in spec.get("grad_groups", {}).items():
        out[name] = math.sqrt(sum(
            sq for path, sq in squares.items() if re.search(pattern, path)))
    return out, (grads if keep_grads else None)


def loss_and_grad_norm(params, batch, spec: dict) -> dict:
    """{"loss", "grad_norm", "mamba_out_rms"}, a key a group of
    ``spec["grad_groups"]`` and, given ``spec["adamw"]``,
    ``"update_norm"``: ``loop.py`` holds every key against the metric of
    that name of the program's first dispatch, all at the
    configuration's one ``rtol``."""
    adamw = spec.get("adamw")
    out, grads = loss_and_grads(params, batch, spec, keep_grads=bool(adamw))
    if adamw:
        out["update_norm"] = _other("joyai").adamw_first_change(
            params, grads, out["grad_norm"], adamw)
    return out

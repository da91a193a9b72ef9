"""granite-4.0-h-micro (Mamba-2 mixers at one group and one NoPE
grouped-query attention layer in ten, every block with a dense SwiGLU
MLP, under Granite's four multipliers and a tied table) as plain
``jax.numpy`` in float32: the configuration's plain reference. It shares
no code with ``ray_tpu/``: it reads the program's parameter tree and the
same batch and computes the model the straightforward way, from the layer
equations (``configs/granite-4.0-h-micro.json`` repeats them):

- ``x_0 = embedding_multiplier * E[token]``;
- a block: ``x = x + residual_multiplier * Mixer(RMSNorm(x))``, then ``x =
  x + residual_multiplier * W_out (silu(g) * u)``, ``[g | u] = W_in
  RMSNorm(x)``;
- a ``mamba`` mixer by **the recurrence as written**, a ``lax.scan`` over
  time, all heads at once, every head reading the one group's ``B_t``,
  ``C_t`` (``references/nemotron_h.py::_recurrence``, a plain helper of
  the same kind: no chunks, no decay squares, no running sums; blocks of
  256 steps under ``jax.checkpoint`` are bookkeeping); the convolution as
  four shifted multiply-adds; the gated RMSNorm over each group's lanes
  (one group: all 4,096);
- the ``attention`` mixer as a masked softmax over a head's whole score
  matrix at the scale ``attention_multiplier`` (1/64, not ``1 /
  sqrt(64)``), each key/value head serving its four query heads, **no
  positions**;
- ``logits = RMSNorm(x_L) E^T / logits_scaling`` against the same table,
  the loss a chunk of rows at a time. The table's gradient is the sum of
  the head's and of the lookup's (the second through
  ``embedding_multiplier``).

**Departures from the published description**: none in the mathematics.
What ``config.json`` does not fix (the initialisers, which the reference
does not read: it takes the program's tree) is the configuration file's
``assumed``.

It runs on the chip after the window, beside the live train state, so it
is frugal with memory and not with time: the gradient is taken **a layer
at a time** as ``references/phi4flash.py`` takes it (the forward pass
keeps each block's input, 67 MB at 8,192 rows; then each block is
differentiated alone from the cotangent of its output), and the
parameters may wait on the host (numpy): a block's are on the device only
while the block runs.

Beside the loss and the gradient's norm it returns ``mamba_out_rms``, the
root mean square of the scans' ``y`` (before the gate) over the Mamba
layers, and a key a group of ``spec["grad_groups"]``
(``grad_norm_mamba_ssm``: ``A_log``, ``D``, ``dt_bias``, the convolution,
the gate norm's scale; ``grad_norm_table``: the tied leaf, both paths;
``grad_norm_attn``). ``spec["adamw"]`` adds the optimizer's first step
(``references/joyai.py::adamw_first_change``) and ``update_norm``;
``spec["operand_dtype"]`` (absent in a run of the benchmark) gives the
reading that the configuration's limit is set against from below: every
matmul operand that the program holds in its compute type rounded to that
type first; the recurrence's ``dt`` and decays, the norms and the softmax
(which the program runs in float32) left alone.
"""

from __future__ import annotations

import math
import re

ROW_BLOCK = 1024        # rows of logits computed at a time


def _other(name: str):
    from benchlib import manifest
    return manifest.load_reference(name)


def _mamba(p, h, spec, rnd):
    """(the mixer's output, the mean square of the scan's ``y``)."""
    import jax
    import jax.numpy as jnp

    plain = _other("nemotron_h")
    rows, t, _ = h.shape
    heads, dim = spec["mamba_heads"], spec["mamba_head_dim"]
    groups, n = spec["ssm_groups"], spec["ssm_state"]
    inner = heads * dim
    zxbcdt = rnd(h) @ rnd(p["in_proj"]["kernel"])
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * groups * n]
    dt = zxbcdt[..., 2 * inner + 2 * groups * n:]
    w, bias = p["conv"]["kernel"], p["conv"]["bias"]
    taps = w.shape[0]
    back = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(bias + sum(back[:, j:j + t] * w[j]
                                 for j in range(taps)))
    x = rnd(xbc[..., :inner]).reshape(rows, t, heads, dim)
    # head j reads group j // (heads / groups): with one group, all the same
    b, c = (jnp.repeat(rnd(part).reshape(rows, t, groups, n),
                       heads // groups, axis=2)
            for part in (xbc[..., inner:inner + groups * n],
                         xbc[..., inner + groups * n:]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = (plain._recurrence(x, dt, -jnp.exp(p["A_log"]), b, c)
         + p["D"][:, None] * x)
    gated = y.reshape(rows, t, inner) * jax.nn.silu(z)
    gated = gated.reshape(rows, t, groups, inner // groups)
    gated = gated / jnp.sqrt((gated * gated).mean(-1, keepdims=True)
                             + spec["rms_eps"])
    gated = gated.reshape(rows, t, inner) * p["gate_norm"]["scale"]
    return rnd(gated) @ rnd(p["out_proj"]["kernel"]), jnp.mean(y * y)


def _attention(p, h, spec, rnd):
    import jax
    import jax.numpy as jnp

    rows, t, _ = h.shape
    heads, kv, dim = spec["n_head"], spec["n_kv_head"], spec["head_dim"]
    scale = spec["attention_multiplier"]
    h = rnd(h)
    q = (h @ rnd(p["q"]["kernel"])).reshape(rows, t, heads, dim)
    k, v = ((h @ rnd(p[name]["kernel"])).reshape(rows, t, kv, dim)
            for name in ("k", "v"))
    # query head j reads key/value head j // (heads / kv)
    read = jnp.arange(heads) // (heads // kv)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    @jax.checkpoint
    def head(at):
        q_j, j = at                                  # [rows, seq, dim]
        s = jnp.einsum("btd,bsd->bts", rnd(q_j), rnd(k[:, :, j])) * scale
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", rnd(w), rnd(v[:, :, j]))

    y = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), read))
    y = jnp.moveaxis(y, 0, 2).reshape(rows, t, heads * dim)
    return rnd(y) @ rnd(p["o"]["kernel"])


def _block(kind: str, spec: dict):
    """(p, x) -> (the block's output, the mean square of its scan's ``y``
    or None)."""
    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))
    norm = _other("nemotron_h")._rms_norm
    eps, m_r = spec["rms_eps"], spec["residual_multiplier"]

    def block(p, x):
        import jax
        import jax.numpy as jnp
        h = norm(x, p["mixer_norm"]["scale"], eps)
        out_sq = None
        if kind == "mamba":
            mixed, out_sq = _mamba(p["mamba"], h, spec, rnd)
        else:
            mixed = _attention(p["attn"], h, spec, rnd)
        x = x + m_r * mixed
        h = rnd(norm(x, p["mlp_norm"]["scale"], eps))
        g, u = jnp.split(h @ rnd(p["mlp"]["gate_up"]["kernel"]), 2, -1)
        x = x + m_r * (rnd(jax.nn.silu(g) * u)
                       @ rnd(p["mlp"]["down"]["kernel"]))
        return x, out_sq
    return block


def _tail(spec: dict):
    """(the final norm's parameters, the table [V, d], x, targets) -> the
    mean cross-entropy of ``RMSNorm(x) E^T / logits_scaling``, a chunk of
    rows at a time."""
    rnd = _other("olmoe")._rounder(spec.get("operand_dtype"))
    norm = _other("nemotron_h")._rms_norm

    def tail(norm_f, table, x, targets):
        import jax
        import jax.numpy as jnp
        h = rnd(norm(x, norm_f["scale"], spec["rms_eps"]))
        h, tg = h.reshape(-1, h.shape[-1]), targets.reshape(-1)
        n = h.shape[0]
        rows = math.gcd(n, ROW_BLOCK)
        head = rnd(table).T

        @jax.checkpoint
        def chunk(part):
            hc, tc = part
            logp = jax.nn.log_softmax(hc @ head / spec["logits_scaling"],
                                      axis=-1)
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
    norm = _other("nemotron_h")._rms_norm
    with jax.default_matmul_precision("highest"):
        table = params["wte"]["embedding"]
        x, out_sq = spec["embedding_multiplier"] * table[tokens], []
        for i, kind in enumerate(spec["layer_types"]):
            x, sq = _block(kind, spec)(params[f"h_{i}"], x)
            if sq is not None:
                out_sq.append(sq)
        logits = (norm(x, params["norm_f"]["scale"], spec["rms_eps"])
                  @ table.T / spec["logits_scaling"])
    return logits, jnp.stack(out_sq)


def loss_and_grads(params, batch, spec: dict, keep_grads: bool = True):
    """(the numbers, the gradient tree or None) of the whole batch at
    ``params``, float32 throughout. ``batch`` is {"tokens", "targets"},
    [rows, seq]. ``spec``: layer_types, mamba_heads, mamba_head_dim,
    ssm_state, ssm_groups, n_head, n_kv_head, head_dim, rms_eps, the four
    multipliers, for the low reading operand_dtype, and ``grad_groups``
    {name: regular expression over a gradient leaf's path,
    ``h_0/mamba/A_log``}: the norm of the leaves each finds is among the
    numbers under its name. Without ``keep_grads`` a block's gradient
    lives only until its squared norm is taken; the kept tree is
    numpy's, on the host."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    tokens, targets = batch["tokens"], batch["targets"]
    kinds = spec["layer_types"]
    forward_of = {k: jax.jit(_block(k, spec)) for k in set(kinds)}
    backward_of = {k: jax.jit(
        lambda p, x, dx, k=k: jax.vjp(
            lambda p, x: _block(k, spec)(p, x)[0], p, x)[1](dx))
        for k in set(kinds)}
    grads, squares = {}, {}     # squares: a leaf's path -> its squared norm

    def took(name, g):
        for path, z in jax.tree_util.tree_flatten_with_path(g)[0]:
            squares["/".join([name, *(k.key for k in path)])] = float(
                jnp.sum(z * z))
        if keep_grads:
            grads[name] = jax.device_get(g)

    with jax.default_matmul_precision("highest"):
        table = params["wte"]["embedding"]
        x = spec["embedding_multiplier"] * table[tokens]
        inputs, out_sq = [], []
        for i, kind in enumerate(kinds):
            inputs.append(x)
            x, sq = forward_of[kind](params[f"h_{i}"], x)
            if sq is not None:
                out_sq.append(float(sq))
        loss, (g_norm, g_head, dx) = jax.jit(jax.value_and_grad(
            _tail(spec), argnums=(0, 1, 2)))(
                params["norm_f"], table, x, targets)
        took("norm_f", g_norm)
        for i in reversed(range(len(kinds))):
            g, dx = backward_of[kinds[i]](params[f"h_{i}"], inputs.pop(), dx)
            took(f"h_{i}", g)
        # the tied table: the head's gradient and the lookup's, the
        # second through the embedding's multiplier
        took("wte", {"embedding": g_head.at[tokens].add(
            spec["embedding_multiplier"] * dx)})
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

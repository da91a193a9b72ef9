"""GPT-2 as plain ``jax.numpy`` in float32: the configuration's plain
reference. It shares no code with ``ray_tpu/models/gpt2.py``: it reads
that model's parameter tree and the same batch and computes the same
loss the straightforward way — whole score matrices, whole logits,
every matmul at the highest precision — and the gradient by
``jax.grad``. The program's step has to land within the
configuration's ``reference.rtol`` of it (``benchlib/checks.py``).

The batch is walked in micro-batches of a few rows a chip so that the
whole logits fit beside the program; the mean over equal micro-batches
is the mean over the batch.
"""

from __future__ import annotations

import math


def _layer_norm(x, p, eps):
    import jax.numpy as jnp
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    import jax.numpy as jnp
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def loss(params, tokens, targets, n_layer: int, eps: float = 1e-5):
    """Mean next-token cross-entropy over every row of the tied
    embedding (the padded rows take part in the softmax, as they do in
    the program)."""
    import jax
    import jax.numpy as jnp

    t = tokens.shape[1]
    wte = params["wte"]["embedding"]
    x = wte[tokens] + params["wpe"]["embedding"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(n_layer):
        p = params[f"h_{i}"]
        a, m = p["attn"], p["mlp"]
        h = _layer_norm(x, p["ln_1"], eps)
        q, k, v = (jnp.einsum("bte,eshd->sbthd", h, a["qkv_kernel"])
                   + a["qkv_bias"][:, None, None])
        s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(q.shape[-1])
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        y = jnp.einsum("bhts,bshd->bthd", w, v)
        x = x + jnp.einsum("bthd,hde->bte", y, a["proj_kernel"]) \
            + a["proj_bias"]
        h = _layer_norm(x, p["ln_2"], eps)
        h = _gelu_new(h @ m["fc"]["kernel"] + m["fc"]["bias"])
        x = x + h @ m["proj"]["kernel"] + m["proj"]["bias"]
    logits = _layer_norm(x, params["ln_f"], eps) @ wte.T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).mean()


def loss_and_grad_norm(params, batch, mesh, n_layer: int,
                       micro_rows_per_chip: int) -> dict:
    """{"loss", "grad_norm"} of the whole batch at ``params``, float32
    throughout. ``batch`` is {"tokens", "targets"}, [rows, seq]; its
    rows may be spread over the mesh's chips, and every micro-batch
    takes ``micro_rows_per_chip`` rows from each."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    chips = mesh.devices.size
    rows, seq = batch["tokens"].shape
    per_chip = rows // chips
    m = min(micro_rows_per_chip, per_chip)
    if rows % chips or per_chip % m:
        raise ValueError(f"{rows} rows do not split into micro-batches "
                         f"of {m} on each of {chips} chips")
    spread = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
    stack_sh = NamedSharding(mesh, P(None, spread or None))

    def stack(x):       # [rows, seq] -> [micro, chips * m, seq]
        x = x.reshape(chips, per_chip // m, m, seq).swapaxes(0, 1)
        return jax.lax.with_sharding_constraint(
            x.reshape(per_chip // m, chips * m, seq), stack_sh)

    @jax.jit
    def run(params, tokens, targets):
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), params)

        def one(carry, xt):
            value, grads = jax.value_and_grad(loss)(params, *xt, n_layer)
            total, acc = carry
            return (total + value,
                    jax.tree_util.tree_map(jnp.add, acc, grads)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        (total, acc), _ = jax.lax.scan(
            one, (jnp.zeros((), jnp.float32), zero),
            (stack(tokens), stack(targets)))
        n = per_chip // m
        sq = sum(jnp.sum((g / n) ** 2)
                 for g in jax.tree_util.tree_leaves(acc))
        return {"loss": total / n, "grad_norm": jnp.sqrt(sq)}

    with jax.default_matmul_precision("highest"):
        out = run(params, batch["tokens"], batch["targets"])
    return {k: float(v) for k, v in out.items()}

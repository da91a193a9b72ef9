"""The bottleneck ResNet (v1.5: stride on the 3x3) as plain ``jax.lax``
in float32: the configuration's plain reference. It shares no code with
``ray_tpu/models/resnet.py``: it reads that model's parameter tree and
the same images and computes the training-mode loss the straightforward
way — convolutions at the highest precision, batch statistics over the
batch — and the gradient by ``jax.grad``.
"""

from __future__ import annotations


def _conv(x, w, stride: int):
    import jax
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, eps):
    import jax.numpy as jnp
    mu = x.mean((0, 1, 2))
    var = ((x - mu) ** 2).mean((0, 1, 2))
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _max_pool_3x3_s2(x):
    import jax
    import jax.numpy as jnp
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")


def loss(params, images, labels, stage_sizes, eps: float = 1e-5):
    """Mean softmax cross-entropy of the net in training mode (batch
    statistics; the running averages do not enter the loss)."""
    import jax
    import jax.numpy as jnp

    x = _conv(images, params["conv_init"]["kernel"], 2)
    x = jax.nn.relu(_batch_norm(x, params["bn_init"], eps))
    x = _max_pool_3x3_s2(x)
    for i, n_blocks in enumerate(stage_sizes):
        for j in range(n_blocks):
            p = params[f"stage{i}_block{j}"]
            stride = 2 if i > 0 and j == 0 else 1
            y = _conv(x, p["conv1"]["kernel"], 1)
            y = jax.nn.relu(_batch_norm(y, p["bn1"], eps))
            y = _conv(y, p["conv2"]["kernel"], stride)
            y = jax.nn.relu(_batch_norm(y, p["bn2"], eps))
            y = _batch_norm(_conv(y, p["conv3"]["kernel"], 1), p["bn3"], eps)
            if "conv_proj" in p:
                x = _batch_norm(_conv(x, p["conv_proj"]["kernel"], stride),
                                p["bn_proj"], eps)
            x = jax.nn.relu(y + x)
    c = params["classifier"]
    logits = x.mean((1, 2)) @ c["kernel"] + c["bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], -1).mean()


def loss_and_grad_norm(params, batch, stage_sizes) -> dict:
    """{"loss", "grad_norm"} at ``params`` on ``batch`` ({"image",
    "label"}), float32 throughout."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(params, images, labels):
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), params)
        value, grads = jax.value_and_grad(loss)(
            params, images.astype(jnp.float32), labels, stage_sizes)
        sq = sum(jnp.sum(g ** 2) for g in jax.tree_util.tree_leaves(grads))
        return {"loss": value, "grad_norm": jnp.sqrt(sq)}

    with jax.default_matmul_precision("highest"):
        out = run(params, batch["image"], batch["label"])
    return {k: float(v) for k, v in out.items()}

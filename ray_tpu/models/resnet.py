"""ResNet for the images/sec north-star benchmark.

Mirrors the reference's harness shape
(``release/air_tests/air_benchmarks/mlperf-train/resnet50_ray_air.py``)
but TPU-first: NHWC layout (XLA TPU native), bfloat16 compute, BatchNorm
state carried as a separate ``batch_stats`` collection, conv kernels
sharded by the pattern table (Cout -> tp when present).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class ResNet50Config:
    num_classes: int = 1000
    stage_sizes: tuple[int, ...] = (3, 4, 6, 3)
    width: int = 64
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def resnet18(**kw) -> "ResNet50Config":
        return ResNet50Config(stage_sizes=(2, 2, 2, 2), **kw)

    @staticmethod
    def tiny(**kw) -> "ResNet50Config":
        kw.setdefault("num_classes", 10)
        kw.setdefault("stage_sizes", (1, 1))
        kw.setdefault("width", 16)
        return ResNet50Config(**kw)


class Bottleneck(nn.Module):
    features: int
    strides: int
    config: ResNet50Config

    @nn.compact
    def __call__(self, x, train: bool):
        cfg = self.config
        conv = partial(nn.Conv, use_bias=False, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype)
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype)
        residual = x
        y = conv(self.features, (1, 1), name="conv1")(x)
        y = nn.relu(norm(name="bn1")(y))
        y = conv(self.features, (3, 3), strides=(self.strides,) * 2,
                 name="conv2")(y)
        y = nn.relu(norm(name="bn2")(y))
        y = conv(self.features * 4, (1, 1), name="conv3")(y)
        y = norm(name="bn3", scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.features * 4, (1, 1),
                            strides=(self.strides,) * 2,
                            name="conv_proj")(residual)
            residual = norm(name="bn_proj")(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    config: ResNet50Config = field(default_factory=ResNet50Config)

    @nn.compact
    def __call__(self, x, train: bool = False):
        cfg = self.config
        # Program scopes (docs/observability.md): the stem is this
        # model's ``embed``, the stages its ``blocks``, pooling and the
        # classifier belong to ``loss`` with the cross-entropy.
        with jax.named_scope("embed"):
            x = x.astype(cfg.dtype)
            x = nn.Conv(cfg.width, (7, 7), strides=(2, 2),
                        use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="conv_init")(x)
            x = nn.BatchNorm(use_running_average=not train,
                             momentum=0.9, epsilon=1e-5, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype,
                             name="bn_init")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        with jax.named_scope("blocks"):
            for i, n_blocks in enumerate(cfg.stage_sizes):
                for j in range(n_blocks):
                    strides = 2 if i > 0 and j == 0 else 1
                    x = Bottleneck(cfg.width * 2 ** i, strides, cfg,
                                   name=f"stage{i}_block{j}")(x, train)
        with jax.named_scope("loss"):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(cfg.num_classes, dtype=jnp.float32,
                         param_dtype=cfg.param_dtype,
                         name="classifier")(x)
        return x

    def init_variables(self, rng, image_size: int = 224,
                       batch_size: int = 2):
        x = jnp.zeros((batch_size, image_size, image_size, 3),
                      dtype=jnp.float32)
        return self.init(rng, x, train=False)


def resnet_loss_fn(model: ResNet):
    """((params, batch_stats), batch) -> (loss, new_batch_stats)."""

    def loss_fn(params, batch_stats, batch):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch["image"], train=True, mutable=["batch_stats"])
        with jax.named_scope("loss"):
            onehot = jax.nn.one_hot(batch["label"], logits.shape[-1])
            loss = -jnp.mean(
                jnp.sum(onehot * jax.nn.log_softmax(logits), axis=-1))
        return loss, mutated["batch_stats"]

    return loss_fn

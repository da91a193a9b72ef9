"""Llama-family decoder in flax, designed for mesh sharding.

Modern-decoder counterpart to GPT-2 (models/gpt2.py): RMSNorm,
rotary position embeddings, SwiGLU MLP, grouped-query attention
(n_kv_head < n_head), no biases, untied LM head optional. Same
TPU-first choices as GPT-2: bf16 compute / f32 params, pluggable
attention (dense/flash local, ring or ulysses over an ``sp`` axis),
logical sharding constraints on activations, optional remat.

Reference analog: the reference ships no model zoo of its own (its
Train library wraps user torch models, SURVEY.md §2.3); this model
family is part of our in-tree flagship set.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import causal_attention


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 22
    n_head: int = 32
    n_kv_head: int = 4               # GQA groups
    n_embd: int = 2048
    intermediate: int = 5632         # SwiGLU hidden
    seq_len: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "auto"          # auto | dense | ring | ulysses
    sp_axis: str = "sp"
    tie_embeddings: bool = True

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        kw.setdefault("vocab_size", 256)
        kw.setdefault("n_layer", 2)
        kw.setdefault("n_head", 4)
        kw.setdefault("n_kv_head", 2)
        kw.setdefault("n_embd", 64)
        kw.setdefault("intermediate", 176)
        kw.setdefault("seq_len", 64)
        return LlamaConfig(**kw)

    @staticmethod
    def tinyllama_1b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)     # defaults above are the 1.1B

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        kw.setdefault("n_layer", 32)
        kw.setdefault("n_head", 32)
        kw.setdefault("n_kv_head", 32)
        kw.setdefault("n_embd", 4096)
        kw.setdefault("intermediate", 11008)
        kw.setdefault("seq_len", 4096)
        return LlamaConfig(**kw)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


def rope_freqs(head_dim: int, seq_len: int, theta: float):
    """[T, head_dim/2] complex rotation angles."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    return jnp.outer(t, inv)                     # [T, D/2]


def apply_rope(x, angles):
    """x: [B, T, H, D]; rotate pairs (even, odd) by per-position
    angles [T, D/2]."""
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    out = jnp.stack([r1, r2], axis=-1)
    return out.reshape(x.shape)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones,
                           (x.shape[-1],), self.param_dtype)
        xf = x.astype(jnp.float32)
        norm = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, attn_fn: Callable, angles):
        cfg = self.config
        B, T, _ = x.shape
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02))
        q = dense(cfg.n_head * cfg.head_dim, name="q")(x)
        k = dense(cfg.n_kv_head * cfg.head_dim, name="k")(x)
        v = dense(cfg.n_kv_head * cfg.head_dim, name="v")(x)
        q = q.reshape(B, T, cfg.n_head, cfg.head_dim)
        k = k.reshape(B, T, cfg.n_kv_head, cfg.head_dim)
        v = v.reshape(B, T, cfg.n_kv_head, cfg.head_dim)
        q = apply_rope(q, angles[:T])
        k = apply_rope(k, angles[:T])
        # GQA: repeat K/V groups up to n_head so the pluggable
        # attention impls (flash/ring/ulysses) see equal head counts.
        # XLA fuses the broadcast; no extra HBM copy materializes.
        rep = cfg.n_head // cfg.n_kv_head
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        y = attn_fn(q, k, v)
        y = y.reshape(B, T, cfg.n_head * cfg.head_dim)
        return dense(cfg.n_embd, name="proj")(y)


class SwiGLU(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02))
        gate = dense(cfg.intermediate, name="gate")(x)
        up = dense(cfg.intermediate, name="up")(x)
        return dense(cfg.n_embd, name="down")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, attn_fn: Callable, angles):
        cfg = self.config
        norm = partial(RMSNorm, eps=cfg.rms_eps, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype)
        x = x + LlamaAttention(cfg, name="attn")(
            norm(name="attn_norm")(x), attn_fn, angles)
        x = x + SwiGLU(cfg, name="mlp")(norm(name="mlp_norm")(x))
        return x


class Llama(nn.Module):
    """Llama-style decoder LM. ``__call__(tokens) -> logits``."""

    config: LlamaConfig
    mesh: Any = None

    def _attn_fn(self) -> Callable:
        cfg = self.config
        if self.mesh is None:
            return causal_attention
        # Which path (ring, the kernel under shard_map, the kernel
        # bare) is the dispatch layer's call, made from the mesh.
        from ray_tpu.ops.attention import make_sharded_causal_attention
        return make_sharded_causal_attention(
            self.mesh, seq_axis=cfg.sp_axis, impl=cfg.attn_impl)

    def _constrain(self, x):
        if self.mesh is None:
            return x
        from ray_tpu.parallel.sharding import constrain
        return constrain(x, self.mesh, "batch", "seq", None)

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        x = wte(tokens)
        x = self._constrain(x)
        angles = rope_freqs(cfg.head_dim, cfg.seq_len, cfg.rope_theta)
        attn_fn = self._attn_fn()
        block_cls = LlamaBlock
        if cfg.remat:
            block_cls = nn.remat(
                LlamaBlock, static_argnums=(2,),
                policy=jax.checkpoint_policies.nothing_saveable)
        for i in range(cfg.n_layer):
            x = block_cls(cfg, name=f"h_{i}")(x, attn_fn, angles)
            x = self._constrain(x)
        x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name="norm_f")(x)
        if return_hidden:
            # For chunked LM-head losses (never materialize full
            # logits); lm_head params exist regardless — init traces
            # the plain __call__ path.
            return x
        if cfg.tie_embeddings:
            logits = jnp.einsum(
                "bte,ve->btv", x.astype(cfg.dtype),
                wte.embedding.astype(cfg.dtype),
                preferred_element_type=jnp.float32)
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False,
                              name="lm_head", dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype)(x)
            logits = logits.astype(jnp.float32)
        return logits

    def init_params(self, rng, batch_size: int = 2):
        tokens = jnp.zeros((batch_size, self.config.seq_len),
                           dtype=jnp.int32)
        return self.init(rng, tokens)["params"]


def llama_loss_fn(model: Llama, fused_ce: bool = True,
                  ce_chunk: int = 2048):
    from ray_tpu.models.gpt2 import (
        chunked_cross_entropy,
        cross_entropy_loss,
    )

    def loss_fn(params, batch):
        if fused_ce:
            h = model.apply({"params": params}, batch["tokens"],
                            return_hidden=True)
            if model.config.tie_embeddings:
                head = params["wte"]["embedding"]        # (V, E)
            else:
                # Dense kernel is (E, V); the einsum folds the
                # transpose into the dot, no materialized copy.
                head = params["lm_head"]["kernel"].T
            return chunked_cross_entropy(
                h, head, batch["targets"], chunk_size=ce_chunk,
                mesh=model.mesh)
        logits = model.apply({"params": params}, batch["tokens"])
        return cross_entropy_loss(logits, batch["targets"])

    return loss_fn

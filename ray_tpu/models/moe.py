"""Switch-style MoE transformer LM.

Every other block's MLP is a top-1-routed mixture of experts
(ops/moe.py math): dense one-hot dispatch/combine einsums keep shapes
static and MXU-friendly, and the experts dimension carries the
"experts" logical axis so an ``ep`` mesh axis shards experts with the
token exchange compiled to ``all_to_all`` by XLA's sharding
propagation under jit — the pjit-idiomatic form of expert parallelism
(SURVEY.md §2.4 row 6; absent from the reference in-tree).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.gpt2 import (
    Block, CausalSelfAttention, GPT2Config, cross_entropy_loss,
)
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.moe import top1_dispatch


@dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 50304
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    seq_len: int = 1024
    num_experts: int = 8
    capacity_factor: float = 2.0
    aux_loss_coeff: float = 0.01
    moe_every: int = 2               # every k-th block is MoE
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "auto"
    sp_axis: str = "sp"

    @staticmethod
    def tiny(**kw) -> "MoEConfig":
        kw.setdefault("vocab_size", 256)
        kw.setdefault("n_layer", 2)
        kw.setdefault("n_head", 4)
        kw.setdefault("n_embd", 64)
        kw.setdefault("seq_len", 64)
        kw.setdefault("num_experts", 4)
        return MoEConfig(**kw)

    def gpt2(self) -> GPT2Config:
        return GPT2Config(
            vocab_size=self.vocab_size, n_layer=self.n_layer,
            n_head=self.n_head, n_embd=self.n_embd,
            seq_len=self.seq_len, dtype=self.dtype,
            param_dtype=self.param_dtype, attn_impl=self.attn_impl,
            sp_axis=self.sp_axis)


class SwitchFFN(nn.Module):
    """Top-1 routed expert MLP over flattened tokens."""

    config: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, D = x.shape
        tokens = x.reshape(B * T, D)
        router = self.param("router", nn.initializers.normal(0.02),
                            (D, cfg.num_experts), cfg.param_dtype)
        w_up = self.param(
            "w_up", nn.initializers.normal(0.02),
            (cfg.num_experts, D, 4 * D), cfg.param_dtype)
        w_down = self.param(
            "w_down", nn.initializers.normal(0.02),
            (cfg.num_experts, 4 * D, D), cfg.param_dtype)
        capacity = max(1, int(cfg.capacity_factor * tokens.shape[0]
                              / cfg.num_experts))
        logits = (tokens.astype(jnp.float32)
                  @ router.astype(jnp.float32))
        dispatch, combine, aux = top1_dispatch(
            logits, cfg.num_experts, capacity)
        dispatch = dispatch.astype(cfg.dtype)
        combine = combine.astype(cfg.dtype)
        xc = tokens.astype(cfg.dtype)
        expert_in = jnp.einsum("tec,td->ecd", dispatch, xc)
        h = nn.gelu(jnp.einsum("ecd,edh->ech", expert_in,
                               w_up.astype(cfg.dtype)))
        out = jnp.einsum("ech,ehd->ecd", h, w_down.astype(cfg.dtype))
        y = jnp.einsum("tec,ecd->td", combine, out)
        self.sow("intermediates", "aux_loss", aux)
        return y.reshape(B, T, D)


class MoEBlock(nn.Module):
    config: MoEConfig

    @nn.compact
    def __call__(self, x, attn_fn: Callable):
        cfg = self.config
        g = cfg.gpt2()
        ln = partial(nn.LayerNorm, epsilon=1e-5, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype)
        x = x + CausalSelfAttention(g, name="attn")(
            ln(name="ln_1")(x), attn_fn, True)
        x = x + SwitchFFN(cfg, name="moe")(ln(name="ln_2")(x))
        return x


class MoETransformer(nn.Module):
    """GPT-2-shaped LM with switch-MoE FFNs every ``moe_every``-th
    block. ``apply`` with ``mutable=["intermediates"]`` to collect the
    router aux losses."""

    config: MoEConfig
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

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        g = cfg.gpt2()
        B, T = tokens.shape
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        wpe = nn.Embed(cfg.seq_len, cfg.n_embd, name="wpe",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.01))
        x = wte(tokens) + wpe(jnp.arange(T)[None, :])
        attn_fn = self._attn_fn()
        for i in range(cfg.n_layer):
            if (i + 1) % cfg.moe_every == 0:
                x = MoEBlock(cfg, name=f"h_{i}")(x, attn_fn)
            else:
                x = Block(g, name=f"h_{i}")(x, attn_fn, True)
        x = nn.LayerNorm(epsilon=1e-5, name="ln_f", dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype)(x)
        if return_hidden:
            return x
        return jnp.einsum("bte,ve->btv", x.astype(cfg.dtype),
                          wte.embedding.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)

    def init_params(self, rng, batch_size: int = 2):
        tokens = jnp.zeros((batch_size, self.config.seq_len),
                           dtype=jnp.int32)
        return self.init(rng, tokens)["params"]


def moe_loss_fn(model: MoETransformer, fused_ce: bool = True,
                ce_chunk: int = 2048):
    """LM loss + router load-balancing aux loss."""
    from ray_tpu.models.gpt2 import chunked_cross_entropy

    def loss_fn(params, batch):
        if fused_ce:
            h, state = model.apply(
                {"params": params}, batch["tokens"],
                return_hidden=True, mutable=["intermediates"])
            lm = chunked_cross_entropy(
                h, params["wte"]["embedding"], batch["targets"],
                chunk_size=ce_chunk, mesh=model.mesh)
        else:
            logits, state = model.apply(
                {"params": params}, batch["tokens"],
                mutable=["intermediates"])
            lm = cross_entropy_loss(logits, batch["targets"])
        aux_vals = jax.tree_util.tree_leaves(
            state.get("intermediates", {}))
        aux = (sum(jnp.asarray(a, jnp.float32).sum()
                   for a in aux_vals) / max(1, len(aux_vals))
               if aux_vals else 0.0)
        return lm + model.config.aux_loss_coeff * aux

    return loss_fn

"""The RMSNorm / RoPE / SwiGLU decoder in flax, designed for mesh sharding.

Modern-decoder counterpart to GPT-2 (models/gpt2.py): RMSNorm,
rotary position embeddings, SwiGLU MLP, grouped-query attention
(n_kv_head < n_head), no biases, untied LM head optional. Same
TPU-first choices as GPT-2: bf16 compute / f32 params, pluggable
attention (dense/flash local, ring or ulysses over an ``sp`` axis),
logical sharding constraints on activations, optional remat.

Which public models the stack expresses, by its config's fields: the
Llama family (``tinyllama_1b``, ``llama2_7b``: interleaved RoPE as the
original checkpoints hold it, dense SwiGLU) and **OLMoE-1B-7B**
(``olmoe_1b_7b``: QK-norm over the whole q and k projections, RoPE in
the half-split ``rotate_half`` layout, an untied head, and in place of
the dense MLP the dropless top-k routed layer of ``ops/moe.py`` with
its load-balancing and router z losses). OLMoE is the benchmark's
second language model (``olmoe-1b-7b.b4-t4096``); no Llama-family
model is a cell.

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
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.moe import routed_ffn
from ray_tpu.ops.remat import MLP_DOWN, MLP_GATE, MLP_UP


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
    rope_layout: str = "interleaved"  # | "half" (rotate_half, as HF)
    qk_norm: bool = False            # RMSNorm over whole q and k
    # The routed FFN (ops/moe.py::routed_ffn) in place of SwiGLU when
    # num_experts > 0: experts of width expert_width, top_k a token.
    num_experts: int = 0
    top_k: int = 0
    expert_width: int = 0
    norm_topk_prob: bool = False
    aux_loss_coef: float = 0.0       # load balancing, beside the LM loss
    z_loss_coef: float = 0.0         # router z-loss

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

    @staticmethod
    def olmoe_1b_7b(**kw) -> "LlamaConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct ``config.json`` (and
        arXiv:2409.02060 for the two loss coefficients): 1.3B active of
        6.9B parameters."""
        base = dict(
            vocab_size=50304, n_layer=16, n_head=16, n_kv_head=16,
            n_embd=2048, intermediate=0, seq_len=4096,
            rope_theta=10000.0, rms_eps=1e-5, tie_embeddings=False,
            rope_layout="half", qk_norm=True, num_experts=64, top_k=8,
            expert_width=1024, norm_topk_prob=False,
            aux_loss_coef=0.01, z_loss_coef=0.001)
        return LlamaConfig(**{**base, **kw})

    @staticmethod
    def tiny_olmoe(**kw) -> "LlamaConfig":
        """OLMoE's shape at test size: 2 layers, 8 experts, top-2."""
        base = dict(
            vocab_size=256, n_layer=2, n_head=4, n_kv_head=4, n_embd=64,
            seq_len=64, num_experts=8, top_k=2, expert_width=32)
        return LlamaConfig.olmoe_1b_7b(**{**base, **kw})

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    def num_params(self) -> int:
        d, v = self.n_embd, self.vocab_size
        attn = (self.n_head + 2 * self.n_kv_head) * self.head_dim * d \
            + self.n_head * self.head_dim * d \
            + (2 * d if self.qk_norm else 0)
        if self.num_experts:
            mlp = self.num_experts * (3 * d * self.expert_width + d)
        else:
            mlp = 3 * d * self.intermediate
        tables = v * d * (1 if self.tie_embeddings else 2)
        return tables + self.n_layer * (attn + mlp + 2 * d) + d


def rope_freqs(head_dim: int, seq_len: int, theta: float):
    """[T, head_dim/2] complex rotation angles."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    return jnp.outer(t, inv)                     # [T, D/2]


def yarn_freqs(rot_dim: int, seq_len: int, theta: float, *, factor: float,
               original_len: int, beta_fast: float, beta_slow: float,
               attention_factor: float | None = None):
    """YaRN (arXiv:2309.00071, ``rope_type: yarn``): ``([T, rot_dim/2]``
    angles, the amplitude on cos and sin). With ``f_i = theta^(-2i /
    rot_dim)`` and ``c(n) = rot_dim ln(original_len / (2 pi n)) / (2 ln
    theta)``, the pair whose wavelength turns ``n`` times in the
    original length: ``low = floor(c(beta_fast))``, ``high =
    ceil(c(beta_slow))``, both clamped to ``[0, rot_dim - 1]``; ``r_i =
    clip((i - low) / (high - low), 0, 1)``; ``inv_i = (f_i / factor)
    r_i + f_i (1 - r_i)``: the fast pairs keep their frequency, the
    slow ones are stretched by ``factor``, a ramp between. The
    amplitude is ``attention_factor``, by default ``0.1 ln(factor) +
    1``; it goes on q's and k's rotated lanes alike, so the scores of
    those lanes grow by its square."""
    import math

    half = rot_dim // 2

    def turns(n: float) -> float:
        return (rot_dim * math.log(original_len / (2 * math.pi * n))
                / (2 * math.log(theta)))
    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), rot_dim - 1)
    if low == high:
        high += 0.001        # the published code's guard
    f = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                         / rot_dim))
    r = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / (high - low),
                 0.0, 1.0)
    inv = f / factor * r + f * (1.0 - r)
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    t = jnp.arange(seq_len, dtype=jnp.float32)
    return jnp.outer(t, inv), float(attention_factor)


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


def apply_rope_half(x, angles):
    """The same rotation in the published ``rotate_half`` layout:
    element i pairs with i + D/2 (not 2i with 2i+1), which is how the
    Hugging Face checkpoints of OLMoE hold their q and k columns."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


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
        if cfg.qk_norm:     # over the whole projection, before the split
            norm = partial(RMSNorm, eps=cfg.rms_eps, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype)
            q = norm(name="q_norm")(q)
            k = norm(name="k_norm")(k)
        q = q.reshape(B, T, cfg.n_head, cfg.head_dim)
        k = k.reshape(B, T, cfg.n_kv_head, cfg.head_dim)
        v = v.reshape(B, T, cfg.n_kv_head, cfg.head_dim)
        rope = apply_rope_half if cfg.rope_layout == "half" else apply_rope
        q = rope(q, angles[:T])
        k = rope(k, angles[:T])
        # GQA: repeat K/V groups up to n_head so the pluggable
        # attention impls (flash/ring/ulysses) see equal head counts.
        # In front of the flash kernel (a custom call, which XLA cannot
        # fuse into) each copy is written to HBM and its cotangent
        # summed over the copies again: PERF.md section 7, ROADMAP B2
        # (GQA-native K/V in the kernel).
        rep = cfg.n_head // cfg.n_kv_head
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        y = attn_fn(q, k, v)
        y = y.reshape(B, T, cfg.n_head * cfg.head_dim)
        return dense(cfg.n_embd, name="proj")(y)


class SwiGLU(nn.Module):
    """``W_d (SiLU(W_g x) * W_u x)``, no bias. The three products carry
    names (``ops/remat.py::MLP_GATE``, ``MLP_UP``, ``MLP_DOWN``) that
    a recomputed block's policy may list (``models/ouro.py``); under any
    other policy, and outside one, a name is the identity."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02))
        gate = checkpoint_name(dense(cfg.intermediate, name="gate")(x),
                               MLP_GATE)
        up = checkpoint_name(dense(cfg.intermediate, name="up")(x), MLP_UP)
        return checkpoint_name(
            dense(cfg.n_embd, name="down")(nn.silu(gate) * up), MLP_DOWN)


class _Experts(nn.Module):
    """The stacked expert weights, as the published tree groups them
    (``mlp.experts``)."""
    config: LlamaConfig

    @nn.compact
    def __call__(self):
        cfg = self.config
        e, d, f = cfg.num_experts, cfg.n_embd, cfg.expert_width
        init = nn.initializers.normal(0.02)
        return (self.param("gate_proj", init, (e, d, f), cfg.param_dtype),
                self.param("up_proj", init, (e, d, f), cfg.param_dtype),
                self.param("down_proj", init, (e, f, d), cfg.param_dtype))


class _Router(nn.Module):
    """``mlp.gate``: the bias-free router's [d, E] kernel."""
    config: LlamaConfig

    @nn.compact
    def __call__(self):
        cfg = self.config
        return self.param("kernel", nn.initializers.normal(0.02),
                          (cfg.n_embd, cfg.num_experts), cfg.param_dtype)


class RoutedFFN(nn.Module):
    """Dropless top-k mixture of SwiGLU experts: ``mlp.gate`` is the
    router, ``mlp.experts`` the stacked weights. Sows each layer's
    load-balancing loss, z-loss and routes per expert into the
    ``moe`` collection for the loss function."""
    config: LlamaConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        y, aux, z, load = routed_ffn(
            x, _Router(cfg, name="gate")(),
            *_Experts(cfg, name="experts")(),
            top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
            mesh=self.mesh)
        self.sow("moe", "stats", {"aux": aux, "z": z, "load": load})
        return y


class LlamaBlock(nn.Module):
    config: LlamaConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, attn_fn: Callable, angles):
        cfg = self.config
        norm = partial(RMSNorm, eps=cfg.rms_eps, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype)
        x = x + LlamaAttention(cfg, name="attn")(
            norm(name="attn_norm")(x), attn_fn, angles)
        mlp = (RoutedFFN(cfg, self.mesh, name="mlp") if cfg.num_experts
               else SwiGLU(cfg, name="mlp"))
        x = x + mlp(norm(name="mlp_norm")(x))
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
        # Program scopes as in models/gpt2.py (docs/observability.md):
        # ``embed``, ``blocks`` (flax puts ``attn`` and ``mlp`` beneath),
        # ``loss``; the step adds ``optimizer``.
        with jax.named_scope("embed"):
            x = wte(tokens)
            x = self._constrain(x)
        angles = rope_freqs(cfg.head_dim, cfg.seq_len, cfg.rope_theta)
        attn_fn = self._attn_fn()
        block_cls = LlamaBlock
        if cfg.remat:
            block_cls = nn.remat(
                LlamaBlock, static_argnums=(2,),
                policy=jax.checkpoint_policies.nothing_saveable)
        with jax.named_scope("blocks"):
            for i in range(cfg.n_layer):
                x = block_cls(cfg, self.mesh, name=f"h_{i}")(
                    x, attn_fn, angles)
                x = self._constrain(x)
            x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="norm_f")(x)
        if return_hidden:
            # For chunked LM-head losses (never materialize full
            # logits); lm_head params exist regardless — init traces
            # the plain __call__ path.
            return x
        with jax.named_scope("loss"):
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


def _moe_report(stats) -> dict:
    """The routed layers' sown statistics as the step's scalars: the
    two losses averaged over the layers, and the largest expert's
    routes over the mean, in the worst layer."""
    layers = [s for per in jax.tree_util.tree_leaves(
        stats, is_leaf=lambda x: isinstance(x, tuple)) for s in per]
    load = jnp.stack([s["load"] for s in layers])
    return {
        "moe_aux_loss": jnp.mean(jnp.stack([s["aux"] for s in layers])),
        "moe_z_loss": jnp.mean(jnp.stack([s["z"] for s in layers])),
        "moe_load_max_over_mean":
            jnp.max(load.max(axis=-1) / load.mean(axis=-1))}


def llama_loss_fn(model: Llama, fused_ce: bool = True,
                  ce_chunk: int = 2048):
    """(params, batch) -> the LM loss; batch = {tokens, targets}.

    A model with routed experts returns ``(loss, report)`` instead
    (``train/step.py`` puts the report's scalars beside the loss):
    loss = LM loss + ``aux_loss_coef`` * load-balancing loss +
    ``z_loss_coef`` * router z-loss, and the report holds ``lm_loss``,
    ``moe_aux_loss``, ``moe_z_loss`` (means over the layers) and
    ``moe_load_max_over_mean`` (worst layer)."""
    from ray_tpu.models.gpt2 import (
        chunked_cross_entropy,
        cross_entropy_loss,
    )
    cfg = model.config

    def lm_loss(params, batch):
        """(LM loss, what the routed layers sowed)."""
        apply = partial(model.apply, {"params": params}, batch["tokens"],
                        mutable=["moe"])
        if fused_ce:
            h, sown = apply(return_hidden=True)
            if cfg.tie_embeddings:
                head = params["wte"]["embedding"]        # (V, E)
            else:
                # Dense kernel is (E, V); the einsum folds the
                # transpose into the dot, no materialized copy.
                head = params["lm_head"]["kernel"].T
            return chunked_cross_entropy(
                h, head, batch["targets"], chunk_size=ce_chunk,
                mesh=model.mesh), sown
        logits, sown = apply()
        return cross_entropy_loss(logits, batch["targets"]), sown

    def loss_fn(params, batch):
        lm, sown = lm_loss(params, batch)
        if not cfg.num_experts:
            return lm
        report = _moe_report(sown["moe"])
        with jax.named_scope("loss"):
            loss = (lm + cfg.aux_loss_coef * report["moe_aux_loss"]
                    + cfg.z_loss_coef * report["moe_z_loss"])
        return loss, {"lm_loss": lm, **report}

    return loss_fn

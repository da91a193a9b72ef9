"""ZAYA1: the CCA + routed-MLP decoder in flax, designed for mesh
sharding.

The public model it expresses is **ZAYA1-8B** (Zyphra, ``model_type:
zaya``, "8.4B-A0.76B": 40 identical layers at a hidden size of 2,048;
arXiv:2511.17127, with CCA from arXiv:2510.04476). Every layer is two
sublayers on a **scaled residual**,

    x <- (a_r * x + b_r) + (a_o * f(RMSNorm(x)) + b_o)

with learned ``[d]`` scales (ones) and biases (zeros) on the stream and
on the sublayer's output (``scale``), and ``f``:

- **CCA**, compressed convolutional attention (``ops/cca.py``): 8 query
  heads over 2 key/value heads of 128, computed at those widths, with
  two causal convolutions, a q-k mean, an L2 norm with a temperature, a
  half-shifted value and RoPE on 64 of the 128 lanes;
- the **routed MLP**: 16 SwiGLU experts, top-1, no shared expert
  (``ops/moe.py::routed_experts``: the dropless sort, the held share
  and the grouped matmuls of the other routed models, given its routes
  by this file). The router is not one matrix. Each layer projects the
  normed stream to a 256-wide **router state**, adds the previous
  layer's state times a learned ``gamma`` (exponential depth
  averaging), and runs a three-layer GeLU MLP over it; the state is a
  second value that a block hands to the next, beside ``x``. The expert
  is the arg-max of ``softmax(z) + b`` (``b`` a balancing bias outside
  the gradient), its weight the probability without ``b``.

A final RMSNorm and the **tied** table: the head is the embedding, and
``zaya_loss_fn`` runs it through the chunked cross-entropy
(``models/gpt2.py``'s tied path).

It is the benchmark's fifth language model (``zaya1-8b.b2-t8192`` runs
five layers with one chip's share of the experts, 8 of 16, and of the
vocabulary). ``RMSNorm`` and ``rope_freqs`` are ``models/llama.py``'s.

Program scopes (docs/observability.md): ``embed``; ``blocks`` with
``h_i/attn`` (``qkv``, ``conv``, ``mix``, ``rope``, ``core``, ``out``
beneath), ``h_i/mlp`` (``router``, then ``routed_experts``' own
``dispatch``, ``experts``, ``combine``) and ``scale`` under each
sublayer's ``*_res``; ``loss``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.llama import RMSNorm, rope_freqs
from ray_tpu.ops import cca
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.moe import held_route_share, routed_experts
from ray_tpu.ops.pallas import program
from ray_tpu.util import tracing


@dataclass(frozen=True)
class ZayaConfig:
    """The keys of a ``zaya`` ``config.json`` under this repo's names;
    the defaults are ZAYA1-8B's."""
    vocab_size: int = 262272
    n_layer: int = 40                   # num_hidden_layers, all "hybrid"
    n_embd: int = 2048
    seq_len: int = 8192
    rms_eps: float = 1e-5
    # CCA
    n_head: int = 8
    n_kv_head: int = 2
    head_dim: int = 128
    conv_taps: tuple[int, int] = (2, 2)     # cca_time0, cca_time1
    rotary_dim: int = 64                # partial_rotary_factor 0.5
    rope_theta: float = 5_000_000.0
    # the routed MLP
    num_experts: int = 16               # the router's width
    expert_width: int = 2048            # moe_intermediate_size
    router_width: int = 256             # router_hidden_size
    # (first, count) of the experts this model holds, as one chip of an
    # expert-parallel deployment does; None: all of them
    experts_held: tuple[int, int] | None = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def zaya1_8b(**kw) -> "ZayaConfig":
        """Zyphra/ZAYA1-8B ``config.json``: 0.76B active of 8.4B."""
        return ZayaConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "ZayaConfig":
        """The same shape at test size: three layers, 4 query heads
        over 2 key/value heads of 16, 8 experts of which 4 are held."""
        base = dict(
            vocab_size=256, n_layer=3, n_embd=64, seq_len=64, n_head=4,
            n_kv_head=2, head_dim=16, rotary_dim=8, rope_theta=10000.0,
            num_experts=8, expert_width=48, router_width=32,
            experts_held=(4, 4))
        return ZayaConfig(**{**base, **kw})

    def __post_init__(self):
        if self.n_head % self.n_kv_head or self.n_kv_head != 2:
            raise ValueError(
                f"{self.n_head} query heads over {self.n_kv_head} "
                "key/value heads: CCA's value is two halves, the token's "
                "own and the previous token's, one a key/value head")

    @property
    def experts_span(self) -> tuple[int, int]:
        """(first, count) of the experts held; all of them by default."""
        return self.experts_held or (0, self.num_experts)

    @property
    def held(self) -> int:
        return self.experts_span[1]

    @property
    def latent(self) -> int:
        """The width CCA's convolutions run at: every query and
        key/value head side by side."""
        return (self.n_head + self.n_kv_head) * self.head_dim

    def layer_params(self) -> dict:
        """Parameters of a layer by part: ``cca`` (three projections,
        two convolutions, the temperature), ``router`` (the state's
        projection, ``gamma``, the MLP, ``b``), the ``experts`` held,
        ``rest`` (two norms, two sublayers' scales and biases)."""
        d, hd, r = self.n_embd, self.head_dim, self.router_width
        heads = self.n_head + self.n_kv_head
        k0, k1 = self.conv_taps
        cca_ = (d * self.latent + d * self.n_kv_head * hd
                + self.n_head * hd * d
                + (k0 + 1) * self.latent + k1 * heads * hd * hd + self.latent
                + self.n_kv_head)
        router = (d * r + r + r + 2 * (r * r + r)
                  + r * self.num_experts + 2 * self.num_experts)
        return {"cca": cca_, "router": router,
                "experts": self.held * 3 * d * self.expert_width,
                "rest": 2 * d + 8 * d}

    def num_params(self) -> int:
        return (self.n_layer * sum(self.layer_params().values())
                + self.vocab_size * self.n_embd + self.n_embd)


def _dense(cfg: ZayaConfig):
    return partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype,
                   kernel_init=nn.initializers.normal(0.02))


def _norm(cfg: ZayaConfig):
    return partial(RMSNorm, eps=cfg.rms_eps, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype)


class _Conv(nn.Module):
    """A causal convolution's ``kernel`` of the given shape (taps
    first) and its ``bias`` over the latent's channels."""
    config: ZayaConfig
    shape: tuple

    @nn.compact
    def __call__(self):
        cfg = self.config
        return (self.param("kernel", nn.initializers.normal(0.02),
                           self.shape, cfg.param_dtype),
                self.param("bias", nn.initializers.zeros, (cfg.latent,),
                           cfg.param_dtype))


class CCA(nn.Module):
    """Compressed convolutional attention (``ops/cca.py`` has the
    equations). ``qk`` holds W_q's and W_k's columns side by side, ``v``
    W_v1's and W_v2's: one matmul each."""
    config: ZayaConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h, attn_fn: Callable, angles):
        cfg = self.config
        hd, g = cfg.head_dim, cfg.n_kv_head
        k0, k1 = cfg.conv_taps
        with jax.named_scope("qkv"):
            qk = _dense(cfg)(cfg.latent, name="qk")(h)
            v = _dense(cfg)(g * hd, name="v")(h)
            v = jnp.concatenate(
                [v[..., :hd], cca.shift_rows(v[..., hd:])], axis=-1)
        conv0 = _Conv(cfg, (k0, cfg.latent), name="conv0")()
        conv1 = _Conv(cfg, (k1, cfg.n_head + g, hd, hd), name="conv1")()
        tau = self.param("temperature", nn.initializers.ones, (g,),
                         jnp.float32)
        o = cca.cca_attention(qk, v, conv0, conv1, tau, angles,
                              n_head=cfg.n_head, n_kv_head=g,
                              attn_fn=attn_fn, mesh=self.mesh)
        with jax.named_scope("out"):
            return _dense(cfg)(cfg.n_embd, name="out")(o)


class _Experts(nn.Module):
    """The stacked weights of the experts held: ``gate_proj`` and
    ``up_proj`` [held, d, f], ``down_proj`` [held, f, d]."""
    config: ZayaConfig

    @nn.compact
    def __call__(self):
        cfg = self.config
        e, d, f = cfg.held, cfg.n_embd, cfg.expert_width
        init = nn.initializers.normal(0.02)
        return (self.param("gate_proj", init, (e, d, f), cfg.param_dtype),
                self.param("up_proj", init, (e, d, f), cfg.param_dtype),
                self.param("down_proj", init, (e, f, d), cfg.param_dtype))


class Router(nn.Module):
    """(h, the previous layer's state) -> (weights [B, T, 1], experts
    [B, T, 1], this layer's state [B, T, r]), all in float32 at the
    highest matmul precision, as the other routers: a bf16 logit flips
    arg-maxes."""
    config: ZayaConfig

    @nn.compact
    def __call__(self, h, state):
        cfg = self.config
        dense = partial(nn.Dense, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype,
                        precision=jax.lax.Precision.HIGHEST,
                        kernel_init=nn.initializers.normal(0.02))
        r = cfg.router_width
        gamma = self.param("gamma", nn.initializers.constant(0.5), (r,),
                           jnp.float32)
        bias = self.param("balance_bias", nn.initializers.zeros,
                          (cfg.num_experts,), jnp.float32)
        state = dense(r, name="down")(h.astype(jnp.float32)) + gamma * state
        z = nn.gelu(dense(r, name="fc1")(state), approximate=False)
        z = nn.gelu(dense(r, name="fc2")(z), approximate=False)
        probs = jax.nn.softmax(dense(cfg.num_experts, name="fc3")(z), axis=-1)
        experts = jnp.argmax(probs + jax.lax.stop_gradient(bias), axis=-1,
                             keepdims=True).astype(jnp.int32)
        return (jnp.take_along_axis(probs, experts, axis=-1), experts, state)


class MoE(nn.Module):
    """The held experts' part of the routed sum and the router state
    for the next layer. Sows the routes each expert received."""
    config: ZayaConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h, state):
        cfg = self.config
        # flax names the module's scope: ``mlp/router``
        weights, experts, state = Router(cfg, name="router")(h, state)
        y, load = routed_experts(
            h, weights, experts, *_Experts(cfg, name="experts")(),
            num_experts=cfg.num_experts, mesh=self.mesh,
            experts_held=cfg.experts_held)
        self.sow("moe", "load", load)
        return y, state


class _Residual(nn.Module):
    """``(a_r * x + b_r) + (a_o * y + b_o)``: the learned scales and
    biases of one sublayer's residual, float32 inside."""
    config: ZayaConfig

    @nn.compact
    def __call__(self, x, y):
        cfg = self.config
        d = (cfg.n_embd,)
        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        a_r = self.param("stream_scale", ones, d, cfg.param_dtype)
        b_r = self.param("stream_bias", zeros, d, cfg.param_dtype)
        a_o = self.param("out_scale", ones, d, cfg.param_dtype)
        b_o = self.param("out_bias", zeros, d, cfg.param_dtype)
        with jax.named_scope("scale"):
            f32 = jnp.float32
            return ((a_r * x.astype(f32) + b_r)
                    + (a_o * y.astype(f32) + b_o)).astype(cfg.dtype)


class Block(nn.Module):
    """(x, the router state) -> (x, the router state): CCA, then the
    routed MLP, each on the normed stream and added to it scaled. The
    norm reads the stream unscaled."""
    config: ZayaConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, state, attn_fn: Callable, angles):
        cfg = self.config
        a = CCA(cfg, self.mesh, name="attn")(
            _norm(cfg)(name="attn_norm")(x), attn_fn, angles)
        x = _Residual(cfg, name="attn_res")(x, a)
        y, state = MoE(cfg, self.mesh, name="mlp")(
            _norm(cfg)(name="mlp_norm")(x), state)
        return _Residual(cfg, name="mlp_res")(x, y), state


class Zaya(nn.Module):
    """``__call__(tokens) -> logits`` (or the final hidden states)."""

    config: ZayaConfig
    mesh: Any = None

    def _attn_fn(self) -> Callable:
        if self.mesh is None:
            return causal_attention
        from ray_tpu.ops.attention import make_sharded_causal_attention
        return make_sharded_causal_attention(self.mesh)

    def _constrain(self, x):
        if self.mesh is None:
            return x
        from ray_tpu.parallel.sharding import constrain
        return constrain(x, self.mesh, "batch", "seq", None)

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        program.refuse(
            self.mesh, "CCA",
            sp="the halo of a sequence split over chips (the convolutions "
               "and the shifted value read the previous token's row, which "
               "the neighbour holds)")
        tracing.note_trace(
            attn_kind="cca", **cca.path_notes(
                (*tokens.shape, cfg.latent), cfg.n_head, cfg.n_kv_head,
                cfg.conv_taps, self.mesh),
            cca_heads=[cfg.n_head, cfg.n_kv_head, cfg.head_dim],
            router_width=cfg.router_width)
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        with jax.named_scope("embed"):
            x = self._constrain(wte(tokens))
        angles = rope_freqs(cfg.rotary_dim, cfg.seq_len, cfg.rope_theta)
        attn_fn = self._attn_fn()
        # the router state of the layer before the first: zeros
        state = jnp.zeros((*tokens.shape, cfg.router_width), jnp.float32)
        with jax.named_scope("blocks"):
            for i in range(cfg.n_layer):
                x, state = Block(cfg, self.mesh, name=f"h_{i}")(
                    x, state, attn_fn, angles)
                x = self._constrain(x)
            x = _norm(cfg)(name="norm_f")(x)
        if return_hidden:
            return x
        with jax.named_scope("loss"):
            return jnp.einsum("bte,ve->btv", x,
                              wte.embedding.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)

    def init_params(self, rng, batch_size: int = 2):
        tokens = jnp.zeros((batch_size, self.config.seq_len), jnp.int32)
        return self.init(rng, tokens)["params"]


def zaya_loss_fn(model: Zaya, ce_chunk: int = 2048):
    """(params, batch) -> ``(loss, report)``; batch = {tokens, targets}.

    The loss is the LM loss alone (no auxiliary loss, no z-loss),
    chunked against the tied table. The report, which ``train/step.py``
    puts beside the loss: ``lm_loss``; ``moe_load``, the routes each
    expert of each layer received, ``[n_layer, E]``;
    ``moe_held_route_share``, of all the routes of all layers the share
    that landed on the experts held, ``moe_absent_route_share``, the
    rest, and ``moe_load_max_over_mean``, the largest expert's routes
    over the mean in the worst layer."""
    from ray_tpu.models.gpt2 import chunked_cross_entropy
    cfg = model.config

    def loss_fn(params, batch):
        hidden, sown = model.apply({"params": params}, batch["tokens"],
                                   return_hidden=True, mutable=["moe"])
        loss = chunked_cross_entropy(
            hidden, params["wte"]["embedding"], batch["targets"],
            chunk_size=ce_chunk, mesh=model.mesh)
        load = jnp.stack([sown["moe"][f"h_{i}"]["mlp"]["load"][0]
                          for i in range(cfg.n_layer)])
        share = held_route_share(load, cfg.experts_span)
        return loss, {
            "lm_loss": loss, "moe_load": load,
            "moe_held_route_share": share,
            "moe_absent_route_share": 1.0 - share,
            "moe_load_max_over_mean": jnp.max(
                load.max(axis=-1) / load.mean(axis=-1))}

    return loss_fn

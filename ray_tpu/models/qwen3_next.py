"""Qwen3-Next: the hybrid Gated DeltaNet / gated softmax attention
decoder in flax, designed for mesh sharding.

The public model it expresses is **Qwen3-Next-80B-A3B-Instruct** (Qwen;
``model_type: qwen3_next``): 48 layers at a hidden size of 2,048 in
periods of ``L L L F`` (``full_attention_interval`` 4: layer ``i``,
counted from 0, is ``F`` when ``(i + 1) % 4 == 0``), every layer with a
routed MLP. The equations, as this file runs them:

- **norms**: ``RMSNorm(x) = x * rsqrt(mean(x^2) + 1e-6) * (1 + w)`` in
  float32, ``w`` from zeros (*zero-centred*): a block's two, the final
  one and the per-head q/k norms of an ``F`` layer (256 wide). The Gated
  DeltaNet's output norm is the plain form, ``w`` from ones;
- every block is ``x = x + Mixer_i(RMSNorm(x))``; ``x = x +
  MoE(RMSNorm(x))``, no bias anywhere;
- **L, Gated DeltaNet** (arXiv:2412.06464; ``ops/kda.py::gdn_scan`` has
  the recurrence): ``[q | k | v | z] = h W_qkvz`` (2,048 + 2,048 + 4,096
  + 4,096: 16 key heads and 32 value heads of 128), ``[b | a] = h W_ba``
  (32 + 32); ``[q | k | v] = silu(conv1d([q | k | v]))``, depthwise,
  causal, 4 taps, no bias, 8,192 channels in one call
  (``ops/conv1d.py::causal_conv1d_silu``); ``beta = sigmoid(b)``, ``g =
  -exp(A_log) * softplus(a + dt_bias)`` in float32, **one a value head**;
  a head's q and k to unit length (``x * rsqrt(sum x^2 + 1e-6)``), q
  times ``128^-1/2`` (where, the recurrence's path decides:
  ``normalize_qk``); **value head j reads key head j // 2**; a head's
  state ``S`` ``[128, 128]`` from zero: ``S <- exp(g_t) S``; ``u_t =
  beta_t (v_t - S^T k_t)``; ``S <- S + k_t u_t^T``; ``o_t = S^T q_t``;
  ``y = RMSNorm_128(o) * w * silu(z)`` a head in float32
  (``ops/gated_norm.py::sigmoid_gated_head_rms_norm`` with ``gate_fn="silu"``);
  ``y W_out`` (4,096 -> 2,048);
- **F, gated attention**: ``q, gate = h W_q, h W_g`` (16 heads of 256
  each; the published ``q_proj`` makes both, a head's 256 + 256 side by
  side: held here as two arrays, a permutation of its columns), ``k``,
  ``v`` 2 heads of 256; ``q = RMSNorm_256(q)``, ``k = RMSNorm_256(k)``
  (zero-centred, a head); the first 64 lanes of q and k rotated in
  halves at theta 1e7 (``partial_rotary_factor`` 0.25); causal softmax
  at scale ``256^-1/2``, query head ``j`` on key/value head ``j // 8``
  (8 copies of K and V under ``repeat``: the equal-width kernels'
  price); ``(core * sigmoid(gate)) W_o``: the gate **elementwise**,
  4,096 wide;
- **MoE** (``ops/moe.py::routed_ffn``): ``p = softmax(x W_r)`` over 512
  in float32; the ten largest, divided by their sum; ``y = sum_i p_i
  E_i(x) + sigmoid(x w_g) * E_s(x)``, every expert ``down(silu(gate x) *
  up x)`` at 512, of which this model may hold a share
  (``experts_held``); the shared expert's gate is one scalar a token.
  No auxiliary loss;
- a final RMSNorm and an **untied** head. No multi-token-prediction
  module (the catalog row's ``config`` has no key for one).

**What a recomputed block keeps** (``remat``: ``nn.remat`` over the
blocks, as ``models/kimi_linear.py``; the note ``blocks_remat_keeps``
lists the names), dearest a byte first: the attention core's output and
row statistics (the flash forward kernel at 256 lanes, 0.14 GB); the
router's float32 product, its choice, chosen probabilities, counts and
``logsumexp`` (``ops/remat.py::ROUTER_KEEPS``, 35 MB a layer: 512 experts
wide; since PR 68 the choice is ``ops/pallas/router_choice.py``'s kernel
pair, which names all of them, so neither it nor a ``top_k`` runs
again); the
mixers' output projections' products (``MIXER_PROJ``, 67 MB: the stream
between a block's halves is then one add); a Gated DeltaNet mixer's
gated output (``GDN_OUT``, 134 MB) and the recurrence's forward
kernel's two results, ``o`` and the state entering every chunk
(``KDA_SCAN_OUT``, ``KDA_SCAN_STATES``: 268 + 537 MB a layer at
16,384 rows of 32 heads), so that the recurrence runs forward once a
layer a step as Kimi-Linear's does (that file's docstring has how the
policy reaches through ``_gdn_core``'s checkpoint). The projections,
the convolution, the decay, the norms, the rotation and the copies of K
and V are made again.

It is the benchmark's fourteenth configuration
(``qwen3-next-80b-a3b.b1-t16384`` runs layers 0-3, one period, with one
chip's share of the experts, 32 of 512, and of the two tables).
``rope_freqs`` and ``SwiGLU`` (through ``models/joyai.py::_swiglu``)
are ``models/llama.py``'s, ``_rotate`` ``models/laguna.py``'s,
``_dense`` and ``_Experts`` ``models/joyai.py``'s, the router's kernel
``models/llama.py::_Router``, the convolution's initialiser
``models/nemotron_h.py``'s; the loss
``models/gpt2.py::chunked_cross_entropy``.

Program scopes (docs/observability.md): ``embed``; ``blocks`` with
``h_i/gdn`` (``qkvz``, ``ba``, ``conv``, ``decay``, ``scan``,
``out_gate``, ``out`` beneath; ``scan`` and, on the XLA path,
``qk_norm`` are opened by ``ops/kda.py::gdn_scan``) in an ``L`` layer
and ``h_i/attn`` (``qkv``, ``qk_norm``, ``rope``, ``repeat``, ``core``,
``gate``, ``out``) in an ``F`` layer, and ``h_i/mlp`` (``router``,
``dispatch``, ``experts``, ``combine``, ``shared``, ``shared_gate``);
``loss``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.joyai import _dense, _Experts, _swiglu
from ray_tpu.models.laguna import _rotate
from ray_tpu.models.llama import _Router, rope_freqs
from ray_tpu.models.nemotron_h import _conv_init
from ray_tpu.ops import conv1d, gated_norm, kda, remat
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.moe import held_route_share, routed_ffn
from ray_tpu.ops.remat import (
    GDN_IN, GDN_OUT, KDA_SCAN_OUT, KDA_SCAN_STATES, MIXER_PROJ, ROUTER_KEEPS)
from ray_tpu.util import tracing

# what a recomputed block keeps (the module docstring)
_BLOCK_KEEPS = (*ROUTER_KEEPS, MIXER_PROJ, GDN_OUT, KDA_SCAN_OUT,
                KDA_SCAN_STATES, GDN_IN)


@dataclass(frozen=True)
class Qwen3NextConfig:
    """The keys of a ``qwen3_next`` ``config.json`` under this repo's
    names; the defaults are Qwen3-Next-80B-A3B-Instruct's."""
    vocab_size: int = 151936
    n_layer: int = 48                   # num_hidden_layers
    n_embd: int = 2048
    seq_len: int = 16384                # the rows a step is built for
    rms_eps: float = 1e-6
    full_attention_interval: int = 4    # layer i is F when (i + 1) % 4 == 0
    # Gated DeltaNet (linear_*)
    gdn_key_heads: int = 16             # linear_num_key_heads
    gdn_value_heads: int = 32           # linear_num_value_heads
    gdn_head_dim: int = 128             # linear_key_head_dim == value's
    conv_kernel: int = 4                # linear_conv_kernel_dim
    gdn_chunk: int = 64
    # gated attention
    n_head: int = 16
    n_kv_head: int = 2
    head_dim: int = 256
    rope_theta: float = 1e7
    partial_rotary: float = 0.25        # partial_rotary_factor
    # the routed MLP
    num_experts: int = 512
    top_k: int = 10                     # num_experts_per_tok
    expert_width: int = 512             # moe_intermediate_size
    shared_width: int = 512             # shared_expert_intermediate_size
    norm_topk_prob: bool = True
    # (first, count) of the experts this model holds; None: all of them
    experts_held: tuple[int, int] | None = None
    remat: bool = False                 # recompute each block in backward
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def qwen3_next_80b_a3b(**kw) -> "Qwen3NextConfig":
        """Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``: 3B active of
        80B parameters."""
        return Qwen3NextConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "Qwen3NextConfig":
        """The same shape at test size: one period ``L L L F``, 2 key
        heads under 4 value heads of 16, 4 query heads over 2 key/value
        heads of 32 with 8 lanes rotated, 16 experts of which 4 are
        held, top-3."""
        base = dict(
            vocab_size=256, n_layer=4, n_embd=64, seq_len=64,
            gdn_key_heads=2, gdn_value_heads=4, gdn_head_dim=16,
            gdn_chunk=16, n_head=4, n_kv_head=2, head_dim=32,
            num_experts=16, top_k=3, expert_width=32, shared_width=32,
            experts_held=(4, 4))
        return Qwen3NextConfig(**{**base, **kw})

    def __post_init__(self):
        if (self.gdn_value_heads % self.gdn_key_heads
                or self.n_head % self.n_kv_head
                or self.rotated_lanes % 2):
            raise ValueError(
                f"{self.gdn_key_heads} key heads under "
                f"{self.gdn_value_heads}, {self.n_kv_head} key/value "
                f"heads under {self.n_head}, {self.rotated_lanes} "
                "rotated lanes")

    def mixer(self, layer: int) -> str:
        """``F`` (gated attention) or ``L`` (Gated DeltaNet) for
        ``layer``, counted from 0."""
        return "F" if (layer + 1) % self.full_attention_interval == 0 else "L"

    @property
    def layer_kinds(self) -> str:
        """The stack's mixers in order, e.g. ``LLLF``."""
        return "".join(self.mixer(i) for i in range(self.n_layer))

    @property
    def rotated_lanes(self) -> int:
        """Lanes of a head that are rotated (the first ones)."""
        return int(self.head_dim * self.partial_rotary)

    @property
    def gdn_key_inner(self) -> int:
        return self.gdn_key_heads * self.gdn_head_dim

    @property
    def gdn_inner(self) -> int:
        return self.gdn_value_heads * self.gdn_head_dim

    @property
    def experts_span(self) -> tuple[int, int]:
        """(first, count) of the experts held; all of them by default."""
        return self.experts_held or (0, self.num_experts)

    @property
    def held(self) -> int:
        return self.experts_span[1]

    def layer_params(self) -> dict:
        """Parameters by part: the ``gdn`` and ``attn`` mixers; the
        ``moe`` outside its routed experts (router, shared expert, its
        gate); one routed ``expert``; ``norms``, a block's two."""
        d, hd = self.n_embd, self.head_dim
        keys, inner, h = self.gdn_key_inner, self.gdn_inner, \
            self.gdn_value_heads
        return {
            "gdn": (d * (2 * keys + 2 * inner) + d * 2 * h
                    + self.conv_kernel * (2 * keys + inner) + 2 * h
                    + self.gdn_head_dim + inner * d),
            "attn": (d * 2 * self.n_head * hd + 2 * d * self.n_kv_head * hd
                     + self.n_head * hd * d + 2 * hd),
            "moe": d * self.num_experts + 3 * d * self.shared_width + d,
            "expert": 3 * d * self.expert_width,
            "norms": 2 * d}

    def num_params(self) -> int:
        per = self.layer_params()
        mixers = sum(per["attn" if self.mixer(i) == "F" else "gdn"]
                     for i in range(self.n_layer))
        return (mixers + self.n_layer * (
            per["norms"] + per["moe"] + self.held * per["expert"])
            + 2 * self.vocab_size * self.n_embd + self.n_embd)


class RMSNorm(nn.Module):
    """The zero-centred RMSNorm: ``x * rsqrt(mean(x^2) + eps) * (1 +
    scale)`` in float32, ``scale`` from zeros."""
    eps: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                           self.param_dtype)
        return _centred_norm(x, scale, self.eps).astype(self.dtype)


def _centred_norm(x, scale, eps: float):
    """Float32; a hook of ``tests/test_qwen3_next.py``'s planted fault."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * (1.0 + scale)


def _norm(cfg: Qwen3NextConfig):
    return functools.partial(RMSNorm, eps=cfg.rms_eps, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype)


def _a_log_init(key, shape, dtype):
    """``A = -exp(A_log)`` with ``exp(A_log)`` uniform in (0, 16): the
    published module's ``A.uniform_(0, 16)`` (a draw of exactly 0 is
    raised to 1e-6: its logarithm has to be a number)."""
    return jnp.log(jnp.maximum(
        jax.random.uniform(key, shape, jnp.float32, 0.0, 16.0), 1e-6)
    ).astype(dtype)


def _decay(a, b_logit, a_log, dt_bias):
    """(``g`` [B, T, H] float32, <= 0; ``beta`` [B, T, H] float32)."""
    f32 = jnp.float32
    g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(f32) + dt_bias)
    return g, jax.nn.sigmoid(b_logit.astype(f32))


def _gdn_core(qkv, z, b_logit, a, w, *, key_heads: int, heads: int,
              chunk: int, eps: float, mesh):
    """A Gated DeltaNet mixer between its input's projections and
    ``W_out``: (the gated, normed output [B, T, H*V]; the mean square
    of the recurrence's output ``o``). ``w``: the mixer's arrays that
    are not dense layers of its input."""
    b, t, _ = qkv.shape
    kd = z.shape[-1] // heads
    keys = key_heads * kd
    with jax.named_scope("conv"):
        qkv = conv1d.causal_conv1d_silu(qkv, w["conv"], mesh=mesh)
    with jax.named_scope("decay"):
        g, beta = _decay(a, b_logit, w["A_log"], w["dt_bias"])
    # a head's q and k go in as the convolution left them, 16 heads
    # wide: the recurrence's path brings them to unit length and hands
    # each value head its key head's (``qk_norm``, ``scan``)
    q = qkv[..., :keys].reshape(b, t, key_heads, kd)
    k = qkv[..., keys:2 * keys].reshape(b, t, key_heads, kd)
    v = qkv[..., 2 * keys:].reshape(b, t, heads, kd)
    o = kda.gdn_scan(q, k, v, g, beta, chunk=chunk, mesh=mesh,
                     normalize_qk=True)
    out_sq = jnp.mean(jnp.square(o))
    with jax.named_scope("out_gate"):
        y = gated_norm.sigmoid_gated_head_rms_norm(
            o.reshape(b, t, heads * kd), z, w["norm"], heads, eps,
            mesh=mesh, gate_fn=_OUT_GATE)
    return y, out_sq


_OUT_GATE = "silu"      # the output gate's function; a test's hook


class GatedDeltaNet(nn.Module):
    """The ``L`` mixer (the module docstring has the equations). Sows
    the mean square of the recurrence's output."""
    config: Qwen3NextConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        heads, keys, inner = (cfg.gdn_value_heads, cfg.gdn_key_inner,
                              cfg.gdn_inner)
        dense = _dense(cfg)
        with jax.named_scope("qkvz"):
            qkvz = checkpoint_name(
                dense(2 * keys + 2 * inner, name="qkvz")(h), GDN_IN)
        with jax.named_scope("ba"):
            ba = checkpoint_name(dense(2 * heads, name="ba")(h), GDN_IN)
        w = {
            "conv": self.param("conv", _conv_init(cfg),
                               (cfg.conv_kernel, 2 * keys + inner),
                               cfg.param_dtype),
            "A_log": self.param("A_log", _a_log_init, (heads,), jnp.float32),
            "dt_bias": self.param("dt_bias", nn.initializers.ones, (heads,),
                                  jnp.float32),
            "norm": self.param("norm", nn.initializers.ones,
                               (cfg.gdn_head_dim,), cfg.param_dtype)}
        y, out_sq = jax.checkpoint(functools.partial(
            _gdn_core, key_heads=cfg.gdn_key_heads, heads=heads,
            chunk=cfg.gdn_chunk, eps=cfg.rms_eps, mesh=self.mesh))(
                qkvz[..., :2 * keys + inner], qkvz[..., 2 * keys + inner:],
                ba[..., :heads], ba[..., heads:], w)
        self.sow("stats", "out_sq", out_sq)
        y = checkpoint_name(y, GDN_OUT)
        with jax.named_scope("out"):
            return checkpoint_name(dense(cfg.n_embd, name="out")(y),
                                   MIXER_PROJ)


def _attn_fn(cfg: Qwen3NextConfig, mesh):
    scale = cfg.head_dim ** -0.5
    if mesh is None:
        return functools.partial(causal_attention, scale=scale)
    from ray_tpu.ops.attention import make_sharded_causal_attention
    return make_sharded_causal_attention(mesh, scale=scale)


def _head_norm(x, scale, eps: float):
    """The zero-centred norm over a head's lanes, x [B, T, H, D]."""
    return _centred_norm(x, scale, eps).astype(x.dtype)


class GatedAttention(nn.Module):
    """The ``F`` mixer: GQA over normed, partly rotated q and k, the
    core's output gated lane by lane."""
    config: Qwen3NextConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h, angles):
        cfg = self.config
        b, t, _ = h.shape
        hd, heads, groups = cfg.head_dim, cfg.n_head, cfg.n_kv_head
        dense = _dense(cfg)
        with jax.named_scope("qkv"):
            q = dense(heads * hd, name="q")(h).reshape(b, t, heads, hd)
            k = dense(groups * hd, name="k")(h).reshape(b, t, groups, hd)
            v = dense(groups * hd, name="v")(h).reshape(b, t, groups, hd)
        with jax.named_scope("qk_norm"):
            q, k = (_head_norm(
                z, self.param(f"{n}_norm", nn.initializers.zeros, (hd,),
                              cfg.param_dtype), cfg.rms_eps)
                for z, n in ((q, "q"), (k, "k")))
        with jax.named_scope("rope"):
            q, k = (_rotate(z, angles[:t], 1.0) for z in (q, k))
        with jax.named_scope("repeat"):
            # the equal-width kernels want as many key/value heads as
            # query heads: 8 copies of each (``models/laguna.py`` has
            # what that costs and whose it is to spare)
            k, v = (jnp.repeat(z, heads // groups, axis=2) for z in (k, v))
        with jax.named_scope("core"):
            o = _attn_fn(cfg, self.mesh)(q, k, v)
        with jax.named_scope("gate"):
            gate = dense(heads * hd, name="gate")(h)
            o = o.reshape(b, t, heads * hd) * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(o.dtype)
        with jax.named_scope("out"):
            return checkpoint_name(dense(cfg.n_embd, name="out")(o),
                                   MIXER_PROJ)


class MoE(nn.Module):
    """The held experts' part of the softmax-routed sum, plus the shared
    expert under its sigmoid gate. Sows the routes each expert
    received."""
    config: Qwen3NextConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        y, _, _, load = routed_ffn(
            x, _Router(cfg, name="gate")(), *_Experts(cfg, name="experts")(),
            top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
            mesh=self.mesh, router="softmax", expert="swiglu",
            experts_held=cfg.experts_held)
        self.sow("moe", "load", load)
        shared = _swiglu(cfg, cfg.shared_width, "shared")(x)
        with jax.named_scope("shared_gate"):
            return y + _shared_gate(
                _dense(cfg)(1, name="shared_gate")(x)) * shared


def _shared_gate(logit):
    """``sigmoid`` of the shared expert's gate, one scalar a token, in
    the logit's dtype; a hook of the tests' planted fault."""
    return jax.nn.sigmoid(logit.astype(jnp.float32)).astype(logit.dtype)


class Block(nn.Module):
    """The layer's mixer (``gdn`` or ``attn``), then its routed MLP,
    each on the normed stream and added to it."""
    config: Qwen3NextConfig
    layer: int
    mesh: Any = None

    @nn.compact
    def __call__(self, x, angles):
        cfg = self.config
        h = _norm(cfg)(name="attn_norm")(x)
        if cfg.mixer(self.layer) == "F":
            x = x + GatedAttention(cfg, self.mesh, name="attn")(h, angles)
        else:
            x = x + GatedDeltaNet(cfg, self.mesh, name="gdn")(h)
        return x + MoE(cfg, self.mesh, name="mlp")(
            _norm(cfg)(name="mlp_norm")(x))


class Qwen3Next(nn.Module):
    """``__call__(tokens) -> logits`` (or the final hidden states)."""

    config: Qwen3NextConfig
    mesh: Any = None

    def _constrain(self, x):
        if self.mesh is None:
            return x
        from ray_tpu.parallel.sharding import constrain
        return constrain(x, self.mesh, "batch", "seq", None)

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        t = tokens.shape[1]
        tracing.note_trace(
            attn_kind="gdn_gated", attn_layers=cfg.layer_kinds,
            attn_gqa=[cfg.n_head, cfg.n_kv_head], attn_head_dim=cfg.head_dim,
            attn_gate="elementwise_sigmoid", rope_kind="half",
            rope_lanes=cfg.rotated_lanes, norm_kind="zero_centred",
            moe_shared_gate="sigmoid", blocks_remat=cfg.remat,
            blocks_remat_keeps=remat.keeps_note(cfg.remat, _BLOCK_KEEPS))
        angles = rope_freqs(cfg.rotated_lanes, t, cfg.rope_theta)
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        with jax.named_scope("embed"):
            x = self._constrain(wte(tokens))
        block = remat.block(Block, cfg.remat, _BLOCK_KEEPS)
        with jax.named_scope("blocks"):
            for i in range(cfg.n_layer):
                x = self._constrain(
                    block(cfg, i, self.mesh, name=f"h_{i}")(x, angles))
            x = _norm(cfg)(name="norm_f")(x)
        if return_hidden:
            # For the chunked loss, which never makes a row's logits;
            # the head's parameters exist regardless: initialisation
            # traces the plain path.
            return x
        with jax.named_scope("loss"):
            return _dense(cfg)(cfg.vocab_size, name="lm_head")(x).astype(
                jnp.float32)

    def init_params(self, rng, batch_size: int = 2):
        """Traced on a short row: no parameter's shape reads the
        sequence, and the untied head's logits over a whole row are not
        made at initialisation."""
        t = min(self.config.seq_len, 128)
        return self.init(rng, jnp.zeros((batch_size, t), jnp.int32))["params"]


def qwen3_next_loss_fn(model: Qwen3Next, ce_chunk: int = 2048):
    """(params, batch) -> ``(loss, report)``; batch = {tokens, targets}.

    The loss is the LM loss alone (no auxiliary loss), chunked against
    the untied head. The report, which ``train/step.py`` puts beside
    the loss: ``lm_loss``; ``moe_load``, the routes each expert of each
    layer received, ``[L, E]``; ``moe_held_route_share``, of all the
    routes of all layers the share that landed on the experts held,
    ``moe_absent_route_share``, the rest, and
    ``moe_load_max_over_mean``, the largest expert's routes over the
    mean in the worst layer; ``gdn_out_rms``, the root mean square of
    the recurrences' output ``o`` over the Gated DeltaNet layers, before
    norm and gate."""
    from ray_tpu.models.gpt2 import chunked_cross_entropy
    cfg = model.config

    def loss_fn(params, batch):
        hidden, sown = model.apply({"params": params}, batch["tokens"],
                                   return_hidden=True,
                                   mutable=["moe", "stats"])
        loss = chunked_cross_entropy(
            hidden, params["lm_head"]["kernel"].T, batch["targets"],
            chunk_size=ce_chunk, mesh=model.mesh)
        load = jnp.stack([sown["moe"][f"h_{i}"]["mlp"]["load"][0]
                          for i in range(cfg.n_layer)])
        share = held_route_share(load, cfg.experts_span)
        report = {
            "lm_loss": loss, "moe_load": load,
            "moe_held_route_share": share,
            "moe_absent_route_share": 1.0 - share,
            "moe_load_max_over_mean": jnp.max(
                load.max(axis=-1) / load.mean(axis=-1))}
        if "stats" in sown:
            report["gdn_out_rms"] = jnp.sqrt(jnp.mean(jnp.stack(
                jax.tree_util.tree_leaves(sown["stats"]))))
        return loss, report

    return loss_fn

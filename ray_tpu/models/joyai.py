"""JoyAI-LLM-Flash: the DeepSeek-V3-shaped decoder in flax, designed for
mesh sharding.

The public model it expresses is **JoyAI-LLM-Flash** (jdopensource,
"48B-A2.7B": 40 layers at a hidden size of 2,048), whose ``config.json``
carries the keys of the DeepSeek-V3 architecture (arXiv:2412.19437):

- every block is ``x = x + MLA(RMSNorm(x))``; ``x = x + MLP_l(RMSNorm(x))``;
- **MLA**, multi-head latent attention (``ops/mla.py``): queries through
  a 1,536-wide normed latent, keys and values through a 512-wide one,
  32 heads of 128 + 64 (the 64 rotary, the key's rotary part one head
  that all share) against values of 128;
- the first ``dense_layers`` blocks (1) have a dense SwiGLU MLP
  (``models/llama.py::SwiGLU``); the others the routed layer
  (``ops/moe.py::routed_ffn``: the float32 sigmoid router over 256
  experts with its selection bias, top-8 renormalised and scaled, SwiGLU
  experts, of which this model may hold a share, ``experts_held``) plus
  a shared SwiGLU expert on every token;
- a final RMSNorm and an untied head;
- ``mtp_depth`` (1) **multi-token-prediction modules** (that paper's
  section 2.2): with ``x_L[i]`` the last block's output at position
  ``i`` before the final norm, ``u_i = W_eh [RMSNorm(Emb(t_{i+1})) ;
  RMSNorm(x_L[i])]``, one more block of the routed kind over ``u``, a
  norm of its own, and the main model's embedding and head, predicting
  ``t_{i+2}``; the loss is ``L_main + mtp_weight * L_mtp``.

**A second model on the same keys: Xing4.0-29B-A4B** (XingChen-AGI,
``model_type`` ``xing4_0``; ``JoyAIConfig.xing4_0_29b_a4b``): hidden
3,584, two leading dense layers, 64 experts of 1,024, top-4, and two
things this file had not expressed:

- **a residual path of several streams** (``hc_mult`` 4;
  ``ops/hyper_connections.py`` has the equations): the state between
  blocks is ``[B, T, n d]``, every sub-layer reads a learned mix of the
  streams and writes back to all of them under a Sinkhorn-normalised
  map. ``hc_mult`` 1 is ``x + f(norm(x))`` with no map parameters,
  traced exactly as before (not mHC with one stream, whose read-in is a
  sigmoid);
- **YaRN** on the rotary lanes (``rope_scaling``: a ``YarnScaling``;
  ``models/llama.py::yarn_freqs`` makes the angles), with the
  DeepSeek-V3 code's **two factors**: with ``m(s) = 0.1 s ln(factor) +
  1`` the amplitude on cos and sin is ``m(mscale) /
  m(mscale_all_dim)`` (1 for this model) and the softmax scale is
  ``(nope + rope)^-1/2 m(mscale_all_dim)^2`` (x 2.00474), the whole
  score, which ``latent_attention(scale=...)`` hands to the kernels.

``remat`` recomputes each block in the backward pass but for its
attention core's output and row statistics (``ops/remat.py::
remat_policy``, as ``models/kimi_linear.py``); what a recomputed block
keeps is its input state, ``hc_mult`` streams wide, its router's
product and choice (``ops/remat.py::ROUTER_KEEPS``), and at ``hc_mult``
> 1 each sub-layer's residual maps with the 25 floats a token their
backward kernel reads (``MAPS_KEEPS``), so the
second pass runs ``pre`` and ``post`` from them and the maps' norm,
product and forward kernel once a step, and each sub-layer's last products
(``_UNDER_MAPS``: under maps ``post``'s backward reads a sub-layer's
output, so ``out_proj``'s, ``down``'s and the routed sum are kept, with
the dense and shared MLPs' ``gate`` and ``up``).

It is the benchmark's fourth language model
(``joyai-llm-flash.b1-t8192`` runs the dense layer, four routed layers
and the MTP module with one chip's share of the experts and of the
vocabulary) and its eleventh (``xing4.0-29b-a4b.b1-t4096``: a dense and
four routed layers under four streams). ``RMSNorm``, ``apply_rope``, ``rope_freqs`` and ``SwiGLU``
are ``models/llama.py``'s, the routed layer ``ops/moe.py``'s, the loss
``models/gpt2.py::chunked_cross_entropy``, twice a step.

Program scopes (docs/observability.md): ``embed``; ``blocks`` with
``h_i/attn`` (``q_down``, ``q_up``, ``kv_down``, ``kv_up``, ``rope``,
``core``, ``out_proj`` beneath) and ``h_i/mlp`` (a routed one:
``router``, ``dispatch``, ``experts``, ``combine``, ``shared``), and the
MTP module's ``mtp/proj`` and ``mtp/h`` (its ``attn`` and ``mlp``);
``loss``, with the MTP module's norm, head and cross-entropy under
``loss/mtp``. At ``hc_mult`` > 1 also ``h_i/hc_attn`` and ``h_i/hc_mlp``
beside ``attn`` and ``mlp`` (``maps``, ``pre``, ``post`` beneath each;
the same under ``mtp/h``), ``embed/hc_expand``, ``blocks/hc_collapse``
and ``mtp/hc_expand``, ``mtp/hc_collapse``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.llama import RMSNorm, SwiGLU, rope_freqs, yarn_freqs
from ray_tpu.models.nemotron_h import _Router   # gate: [d, E] and its bias
from ray_tpu.ops import hyper_connections as hc, remat
from ray_tpu.ops.mla import UpProjections, latent_attention
from ray_tpu.ops.moe import held_route_share, routed_ffn
from ray_tpu.ops.pallas import program
from ray_tpu.ops.remat import (
    MAPS_KEEPS, MIXER_PROJ, MLP_DOWN, MLP_GATE, MLP_UP, MOE_OUT, ROUTER_KEEPS)
from ray_tpu.util import tracing


@dataclass(frozen=True)
class YarnScaling:
    """A ``rope_scaling`` group of ``type: yarn`` as the DeepSeek-V3 code
    reads it (``models/llama.py::yarn_freqs`` has the frequencies). With
    ``m(s) = 0.1 s ln(factor) + 1``: the amplitude on cos and sin is
    ``m(mscale) / m(mscale_all_dim)`` and the softmax scale is
    multiplied by ``m(mscale_all_dim)^2``, the whole score, nope and
    rope parts alike (``mscale_all_dim`` 0: by 1)."""
    factor: float
    original_len: int                   # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def _m(self, s: float) -> float:
        return 0.1 * s * math.log(max(self.factor, 1.0)) + 1.0

    @property
    def amplitude(self) -> float:
        return self._m(self.mscale) / self._m(self.mscale_all_dim)

    @property
    def score_factor(self) -> float:
        return self._m(self.mscale_all_dim) ** 2


@dataclass(frozen=True)
class JoyAIConfig:
    """The keys of a ``joyai_llm_flash`` or ``xing4_0``
    (DeepSeek-V3-shaped) ``config.json`` under this repo's names; the
    defaults are JoyAI-LLM-Flash's."""
    vocab_size: int = 129280
    n_layer: int = 40                   # num_hidden_layers
    n_embd: int = 2048
    seq_len: int = 8192
    rms_eps: float = 1e-6
    # MLA
    n_head: int = 32
    q_rank: int = 1536                  # q_lora_rank
    kv_rank: int = 512                  # kv_lora_rank
    nope_dim: int = 128                 # qk_nope_head_dim
    rope_dim: int = 64                  # qk_rope_head_dim
    v_dim: int = 128                    # v_head_dim
    rope_theta: float = 32_000_000.0
    rope_scaling: YarnScaling | None = None     # None: rope_freqs
    # the MLPs
    dense_layers: int = 1               # first_k_dense_replace
    dense_width: int = 7168             # intermediate_size
    num_experts: int = 256              # the router's width
    top_k: int = 8
    expert_width: int = 768             # moe_intermediate_size
    shared_width: int = 768             # n_shared_experts x that
    norm_topk_prob: bool = True
    route_scale: float = 2.5
    # (first, count) of the experts this model holds, as one chip of an
    # expert-parallel deployment does; None: all of them
    experts_held: tuple[int, int] | None = None
    # multi-token prediction
    mtp_depth: int = 1                  # num_nextn_predict_layers
    mtp_weight: float = 0.3
    # the residual path (ops/hyper_connections.py): 1 is x + f(norm(x)),
    # no maps; n > 1 is n streams under mHC's maps round every sub-layer
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0          # mhc_h_res_clamp_min / max
    remat: bool = False                 # recompute each block in backward
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def joyai_llm_flash(**kw) -> "JoyAIConfig":
        """jdopensource/JoyAI-LLM-Flash ``config.json``: 2.7B active of
        48B parameters."""
        return JoyAIConfig(**kw)

    @staticmethod
    def xing4_0_29b_a4b(**kw) -> "JoyAIConfig":
        """XingChen-AGI/Xing4.0-29B-A4B ``config.json`` (``xing4_0``):
        4B active of 29B parameters; the same keys plus four residual
        streams under mHC, YaRN on the rotary lanes and two leading
        dense layers."""
        base = dict(
            vocab_size=131072, n_layer=40, n_embd=3584, seq_len=4096,
            q_rank=768, rope_theta=10000.0,
            rope_scaling=YarnScaling(
                factor=64.0, original_len=4096, beta_fast=32.0,
                beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
            dense_layers=2, dense_width=9216, num_experts=64, top_k=4,
            expert_width=1024, shared_width=1024, route_scale=2.0,
            hc_mult=4)
        return JoyAIConfig(**{**base, **kw})

    @staticmethod
    def tiny_xing(**kw) -> "JoyAIConfig":
        """``xing4_0_29b_a4b`` at test size: ``tiny`` with four streams
        and YaRN over a 16-token original length."""
        base = dict(hc_mult=4, top_k=2, route_scale=2.0,
                    rope_scaling=YarnScaling(
                        factor=4.0, original_len=16, beta_fast=4.0,
                        mscale_all_dim=1.0))
        return JoyAIConfig.tiny(**{**base, **kw})

    @staticmethod
    def tiny(**kw) -> "JoyAIConfig":
        """The same shape at test size: a dense layer, two routed ones
        with 16 experts of which 4 are held, top-3, the MTP module."""
        base = dict(
            vocab_size=256, n_layer=3, n_embd=64, seq_len=64, n_head=4,
            q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
            rope_theta=10000.0, dense_width=160, num_experts=16, top_k=3,
            expert_width=32, shared_width=32, experts_held=(4, 4))
        return JoyAIConfig(**{**base, **kw})

    def __post_init__(self):
        if self.mtp_depth not in (0, 1):
            raise NotImplementedError(
                f"mtp_depth={self.mtp_depth}: one multi-token-prediction "
                "module or none")
        if not 0 <= self.dense_layers <= self.n_layer:
            raise ValueError(f"{self.dense_layers} dense layers of "
                             f"{self.n_layer}")
        if self.hc_mult < 1:
            raise ValueError(f"hc_mult={self.hc_mult}: 1 (no "
                             "hyper-connections) or a number of streams")

    @property
    def experts_span(self) -> tuple[int, int]:
        """(first, count) of the experts held; all of them by default."""
        return self.experts_held or (0, self.num_experts)

    @property
    def held(self) -> int:
        return self.experts_span[1]

    @property
    def mla_scale(self) -> float:
        """The softmax scale: ``(nope + rope)^-1/2``, times YaRN's."""
        scale = (self.nope_dim + self.rope_dim) ** -0.5
        return scale * (self.rope_scaling.score_factor
                        if self.rope_scaling else 1.0)

    @property
    def rope_amplitude(self) -> float:
        """YaRN's factor on cos and sin; 1 without a ``rope_scaling``."""
        return self.rope_scaling.amplitude if self.rope_scaling else 1.0

    def hc_params(self) -> int:
        """The residual maps of one block (two sub-layers): ``phi``,
        ``b`` and three gates each; 0 at ``hc_mult`` 1."""
        n = self.hc_mult
        if n == 1:
            return 0
        return 2 * ((n * self.n_embd + 1) * hc.map_width(n) + 3)

    def layer_params(self) -> dict:
        """Parameters of each part: ``mla`` (the five projections and
        the two latent norms), a ``dense`` and a ``routed`` block (MLA,
        the MLP and the block's two norms; the router's bias counts),
        the ``mtp`` module (a routed block, ``W_eh`` and three norms);
        every block counts its residual maps (``hc_params``)."""
        d, h = self.n_embd, self.n_head
        mla = (d * self.q_rank + self.q_rank
               + self.q_rank * h * (self.nope_dim + self.rope_dim)
               + d * (self.kv_rank + self.rope_dim) + self.kv_rank
               + self.kv_rank * h * (self.nope_dim + self.v_dim)
               + h * self.v_dim * d)
        routed = (mla + 2 * d + d * self.num_experts + self.num_experts
                  + 3 * d * self.shared_width
                  + self.held * 3 * d * self.expert_width + self.hc_params())
        return {"mla": mla,
                "dense": (mla + 2 * d + 3 * d * self.dense_width
                          + self.hc_params()),
                "routed": routed, "mtp": routed + 2 * d * d + 3 * d}

    def num_params(self) -> int:
        per = self.layer_params()
        return (self.dense_layers * per["dense"]
                + (self.n_layer - self.dense_layers) * per["routed"]
                + self.mtp_depth * per["mtp"]
                + 2 * self.vocab_size * self.n_embd + self.n_embd)


@dataclass(frozen=True)
class _MLPWidths:
    """What ``models/llama.py::SwiGLU`` reads of a config."""
    n_embd: int
    intermediate: int
    dtype: Any
    param_dtype: Any


def _swiglu(cfg: JoyAIConfig, width: int, name: str):
    return SwiGLU(_MLPWidths(cfg.n_embd, width, cfg.dtype, cfg.param_dtype),
                  name=name)


def _dense(cfg: JoyAIConfig):
    return partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype,
                   kernel_init=nn.initializers.normal(0.02))


def _norm(cfg: JoyAIConfig):
    return partial(RMSNorm, eps=cfg.rms_eps, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype)


class _Down(nn.Module):
    """A down projection: ``proj`` to ``rank + extra`` and the RMSNorm
    of the first ``rank`` (the latent); the ``extra`` columns (the
    shared rotary key) pass unnormed."""
    config: JoyAIConfig
    rank: int
    extra: int = 0

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        z = _dense(cfg)(self.rank + self.extra, name="proj")(h)
        c = _norm(cfg)(name="norm")(z[..., :self.rank])
        return c, z[..., self.rank:]


class _Up(nn.Module):
    """An up projection's arrays, by part (``ops/mla.py``): ``widths``
    maps a part's name to its columns."""
    config: JoyAIConfig
    rank: int
    widths: tuple

    @nn.compact
    def __call__(self):
        cfg = self.config
        return tuple(
            self.param(name, nn.initializers.normal(0.02),
                       (self.rank, cfg.n_head * width), cfg.param_dtype)
            for name, width in self.widths)


class LatentAttention(nn.Module):
    """MLA (``ops/mla.py`` has the equations and the choices)."""
    config: JoyAIConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h, angles):
        cfg = self.config
        c_q, _ = _Down(cfg, cfg.q_rank, name="q_down")(h)
        c_kv, k_r = _Down(cfg, cfg.kv_rank, cfg.rope_dim, name="kv_down")(h)
        q_nope, q_rope = _Up(cfg, cfg.q_rank, (("nope", cfg.nope_dim),
                                               ("rope", cfg.rope_dim)),
                             name="q_up")()
        k_nope, v = _Up(cfg, cfg.kv_rank, (("k", cfg.nope_dim),
                                           ("v", cfg.v_dim)),
                        name="kv_up")()
        o = latent_attention(
            c_q, c_kv, k_r, UpProjections(q_nope, q_rope, k_nope, v), angles,
            n_head=cfg.n_head, mesh=self.mesh, scale=cfg.mla_scale,
            rope_amplitude=cfg.rope_amplitude)
        return checkpoint_name(
            _dense(cfg)(cfg.n_embd, name="out_proj")(o), MIXER_PROJ)


class _Experts(nn.Module):
    """The stacked weights of the experts held: ``gate_proj`` and
    ``up_proj`` [held, d, f], ``down_proj`` [held, f, d]."""
    config: JoyAIConfig

    @nn.compact
    def __call__(self):
        cfg = self.config
        e, d, f = cfg.held, cfg.n_embd, cfg.expert_width
        init = nn.initializers.normal(0.02)
        return (self.param("gate_proj", init, (e, d, f), cfg.param_dtype),
                self.param("up_proj", init, (e, d, f), cfg.param_dtype),
                self.param("down_proj", init, (e, f, d), cfg.param_dtype))


class MoE(nn.Module):
    """The held experts' part of the routed sum, plus the shared
    expert. Sows the routes each expert received."""
    config: JoyAIConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        router_w, select_bias = _Router(cfg, name="gate")()
        y, _, _, load = routed_ffn(
            x, router_w, *_Experts(cfg, name="experts")(), top_k=cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob, mesh=self.mesh,
            router="sigmoid", select_bias=select_bias,
            route_scale=cfg.route_scale, expert="swiglu",
            experts_held=cfg.experts_held)
        self.sow("moe", "load", load)
        # the routed sum as the layer returns it, in front of the shared
        # expert's: what a recomputed block under residual maps keeps of
        # the held experts (``_keeps``)
        y = checkpoint_name(y, MOE_OUT)
        return y + _swiglu(cfg, cfg.shared_width, "shared")(x)


class _ResidualMaps(nn.Module):
    """One sub-layer's residual maps (``ops/hyper_connections.py``):
    ``phi``, ``b`` and the gates ``alpha`` = (pre, post, res), and from
    the state ``H_pre``, ``H_post`` and ``H_res`` under the scope
    ``maps``. ``b`` starts as normal(1.0), not as the identity mHC
    trains from: at the initial parameters no two streams are then
    equal and ``H_res`` is neither uniform, symmetric nor a permutation,
    which is what a comparison with a reference needs to see the maps.
    Sows the largest ``|rowsum(H_res) - 1|``."""
    config: JoyAIConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        n, width = cfg.hc_mult, hc.map_width(cfg.hc_mult)
        phi = self.param("phi", nn.initializers.normal(0.02),
                         (n * cfg.n_embd, width), cfg.param_dtype)
        b = self.param("b", nn.initializers.normal(1.0), (width,),
                       cfg.param_dtype)
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,),
                           cfg.param_dtype)
        with jax.named_scope("maps"):
            maps = hc.hc_maps(x, phi, b, alpha, n=n,
                              iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
                              clamp=cfg.hc_res_clamp, mesh=self.mesh)
            self.sow("stats", "res_row_err", hc.res_row_err(maps[2]))
        return maps


def _around(cfg: JoyAIConfig, name: str, f, x, mesh=None):
    """``x + f(x)``, or the sub-layer ``f`` under its residual maps
    (scope and parameters ``hc_<name>``, beside the sub-layer's own). A
    function and not a method of ``Block``: flax would put the method's
    name into the scope path."""
    if cfg.hc_mult == 1:
        return x + f(x)
    scope = f"hc_{name}"
    h_pre, h_post, h_res = _ResidualMaps(cfg, mesh, name=scope)(x)
    with jax.named_scope(scope), jax.named_scope("pre"):
        u = hc.hc_pre(x, h_pre)
    y = f(u)
    with jax.named_scope(scope), jax.named_scope("post"):
        return hc.hc_post(x, y, h_post, h_res)


class Block(nn.Module):
    """Attention, then the MLP of the block's kind (``routed`` or the
    dense SwiGLU), each on the normed stream and added to it; at
    ``hc_mult`` > 1 each on the normed mix of the streams that its
    ``hc_attn`` / ``hc_mlp`` maps read (``pre``) and written back to all
    of them (``post``)."""
    config: JoyAIConfig
    routed: bool
    mesh: Any = None

    @nn.compact
    def __call__(self, x, angles):
        cfg = self.config
        attn = LatentAttention(cfg, self.mesh, name="attn")
        attn_norm = _norm(cfg)(name="attn_norm")
        x = _around(cfg, "attn", lambda u: attn(attn_norm(u), angles), x,
                    self.mesh)
        mlp = (MoE(cfg, self.mesh, name="mlp") if self.routed
               else _swiglu(cfg, cfg.dense_width, "mlp"))
        mlp_norm = _norm(cfg)(name="mlp_norm")
        return _around(cfg, "mlp", lambda u: mlp(mlp_norm(u)), x, self.mesh)


# what a recomputed block keeps at ``hc_mult`` > 1 beside its router's:
# each sub-layer's residual maps' (on the maps' XLA path nothing carries
# them), each sub-layer's output as ``post`` reads it
# (``post``'s backward reads it too, for the write-in map's gradient: under
# maps a sub-layer's last product is read, where ``x + f(x)`` reads none):
# ``out_proj``'s product, a dense or shared MLP's ``down`` and the routed
# sum; and the dense and shared MLPs' ``gate`` and ``up``. The state
# between the two sub-layers is not kept: with it (117 MB a block) the
# second pass's passes over the streams moved between ``pre`` and ``post``
# and the step did not (PERF.md section 6, PR 70)
_UNDER_MAPS = (*MAPS_KEEPS, MIXER_PROJ, MLP_DOWN, MOE_OUT, MLP_GATE, MLP_UP)


def _keeps(cfg: JoyAIConfig) -> tuple[str, ...]:
    """The names a recomputed block keeps beside its attention core's
    (``ops/remat.py`` has what each is): its router's, so that the
    second pass runs neither the float32 product nor the choice again,
    and at ``hc_mult`` > 1 ``_UNDER_MAPS``."""
    return (*ROUTER_KEEPS, *(_UNDER_MAPS if cfg.hc_mult > 1 else ()))


class MTP(nn.Module):
    """One multi-token-prediction module up to its block's output:
    ``proj`` (the two norms and ``W_eh`` over [embedding ; stream]) and
    ``h``, a block of the routed kind; at ``hc_mult`` > 1 the block
    runs under the same residual path, ``n`` copies of ``u`` in and the
    sum of the streams out."""
    config: JoyAIConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, next_emb, angles):
        cfg = self.config
        with jax.named_scope("proj"):
            u = _dense(cfg)(cfg.n_embd, name="eh_proj")(jnp.concatenate(
                [_norm(cfg)(name="enorm")(next_emb),
                 _norm(cfg)(name="hnorm")(x)], axis=-1))
        if cfg.hc_mult > 1:
            with jax.named_scope("hc_expand"):
                u = hc.hc_expand(u, cfg.hc_mult)
        u = remat.block(Block, cfg.remat, _keeps(cfg))(
            cfg, True, self.mesh, name="h")(u, angles)
        if cfg.hc_mult > 1:
            with jax.named_scope("hc_collapse"):
                u = hc.hc_collapse(u, cfg.hc_mult)
        return u


class JoyAI(nn.Module):
    """``__call__(tokens, next_tokens) -> (logits, logits_mtp)`` (or the
    two normed hidden states). ``next_tokens[i]`` is ``t_{i+1}``, what
    the MTP module embeds beside position ``i``'s stream; by default
    ``tokens`` rolled by one. Without an MTP module the second of each
    pair is None."""

    config: JoyAIConfig
    mesh: Any = None

    def _constrain(self, x):
        if self.mesh is None:
            return x
        from ray_tpu.parallel.sharding import constrain
        return constrain(x, self.mesh, "batch", "seq", None)

    @nn.compact
    def __call__(self, tokens, next_tokens=None, return_hidden: bool = False):
        cfg = self.config
        n = cfg.hc_mult
        tracing.note_trace(
            attn_kind="mla", mla_ranks=[cfg.q_rank, cfg.kv_rank],
            mla_qk_dims=[cfg.nope_dim, cfg.rope_dim], mla_v_dim=cfg.v_dim,
            mtp_depth=cfg.mtp_depth, mtp_weight=cfg.mtp_weight,
            dense_layers=cfg.dense_layers)
        if cfg.remat:
            tracing.note_trace(
                blocks_remat=True,
                blocks_remat_keeps=remat.keeps_note(True, _keeps(cfg)))
        if n > 1:
            program.refuse(self.mesh, "hyper-connections", **hc.SPLIT_STATE)
            maps_path = hc.hc_maps_path(
                (*tokens.shape, n * cfg.n_embd), n, self.mesh)
            tracing.note_trace(
                hc_mult=n, hc_sinkhorn_iters=cfg.hc_sinkhorn_iters,
                hc_state_dtype=jnp.dtype(cfg.dtype).name,
                hc_maps_path=maps_path,
                hc_maps_block=(hc.MAPS_BLOCK_TOKENS if maps_path == "pallas"
                               else 0))
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        with jax.named_scope("embed"):
            x = self._constrain(wte(tokens))
            if n > 1:
                with jax.named_scope("hc_expand"):
                    x = self._constrain(hc.hc_expand(x, n))
        if cfg.rope_scaling:
            # the amplitude goes on in ops/mla.py (cfg.rope_amplitude)
            y = cfg.rope_scaling
            tracing.note_trace(rope_kind="yarn")
            angles, _ = yarn_freqs(
                cfg.rope_dim, cfg.seq_len, cfg.rope_theta, factor=y.factor,
                original_len=y.original_len, beta_fast=y.beta_fast,
                beta_slow=y.beta_slow, attention_factor=1.0)
        else:
            angles = rope_freqs(cfg.rope_dim, cfg.seq_len, cfg.rope_theta)
        h_mtp = None
        block = remat.block(Block, cfg.remat, _keeps(cfg))
        with jax.named_scope("blocks"):
            for i in range(cfg.n_layer):
                x = block(cfg, i >= cfg.dense_layers, self.mesh,
                          name=f"h_{i}")(x, angles)
                x = self._constrain(x)
            if n > 1:
                with jax.named_scope("hc_collapse"):
                    self.sow("stats", "stream_spread",
                             hc.stream_spread(x, n))
                    x = self._constrain(hc.hc_collapse(x, n))
            h = _norm(cfg)(name="norm_f")(x)
            if cfg.mtp_depth:
                if next_tokens is None:
                    next_tokens = jnp.roll(tokens, -1, axis=1)
                with jax.named_scope("mtp"), jax.named_scope("proj"):
                    next_emb = wte(next_tokens)
                h_mtp = self._constrain(MTP(cfg, self.mesh, name="mtp")(
                    x, next_emb, angles))
        head = nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head",
                        dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02))
        with jax.named_scope("loss"):
            if cfg.mtp_depth:
                with jax.named_scope("mtp"):
                    h_mtp = _norm(cfg)(name="mtp_norm")(h_mtp)
            if return_hidden:   # lm_head's parameters come from init's call
                return h, h_mtp
            logits = head(h).astype(jnp.float32)
            with jax.named_scope("mtp"):
                logits_mtp = (head(h_mtp).astype(jnp.float32)
                              if cfg.mtp_depth else None)
        return logits, logits_mtp

    def init_params(self, rng, batch_size: int = 2):
        tokens = jnp.zeros((batch_size, self.config.seq_len), jnp.int32)
        return self.init(rng, tokens)["params"]


def mtp_targets(targets):
    """The MTP module's targets from the main ones: position ``i``
    predicts ``t_{i+2}`` = ``targets[i + 1]``; the last position of a
    row has none and is masked (-1)."""
    return jnp.concatenate(
        [targets[:, 1:], jnp.full_like(targets[:, :1], -1)], axis=1)


def joyai_loss_fn(model: JoyAI, ce_chunk: int = 2048):
    """(params, batch) -> ``(loss, report)``; batch = {tokens, targets}.

    ``loss = lm_loss + mtp_weight * mtp_loss``: the next-token
    cross-entropy of the main head, and the MTP module's, the mean over
    the ``T - 1`` positions of a row that have a second-next token, both
    chunked against the one untied head. The report, which
    ``train/step.py`` puts beside the loss: ``lm_loss``, ``mtp_loss``;
    ``moe_held_route_share``, of all the routes of all routed layers
    (the MTP module's among them) the share that landed on the experts
    held, ``moe_absent_route_share``, the rest, and
    ``moe_load_max_over_mean``, the largest expert's routes over the
    mean in the worst layer; and at ``hc_mult`` > 1 ``hc_res_row_err``,
    the largest ``|rowsum(H_res) - 1|`` over sub-layers and tokens
    (what the Sinkhorn iterations leave), and ``hc_stream_spread``, the
    RMS of ``X_L[i] - mean_i X_L[i]`` over the RMS of ``X_L`` after the
    last block (0 if the streams collapsed into one)."""
    from ray_tpu.models.gpt2 import chunked_cross_entropy
    cfg = model.config

    def loss_fn(params, batch):
        (h, h_mtp), sown = model.apply(
            {"params": params}, batch["tokens"], batch["targets"],
            return_hidden=True, mutable=["moe", "stats"])
        head = params["lm_head"]["kernel"].T
        ce = partial(chunked_cross_entropy, chunk_size=ce_chunk,
                     mesh=model.mesh)
        loss = lm = ce(h, head, batch["targets"])
        report = {"lm_loss": lm}
        if cfg.mtp_depth:
            with jax.named_scope("loss"), jax.named_scope("mtp"):
                mtp = ce(h_mtp, head, mtp_targets(batch["targets"]))
                loss = lm + cfg.mtp_weight * mtp
            report["mtp_loss"] = mtp
        if "moe" in sown:
            load = jnp.stack(jax.tree_util.tree_leaves(sown["moe"]))
            share = held_route_share(load, cfg.experts_span)
            report.update(
                moe_held_route_share=share,
                moe_absent_route_share=1.0 - share,
                moe_load_max_over_mean=jnp.max(
                    load.max(axis=-1) / load.mean(axis=-1)))
        if "stats" in sown:     # hc_mult > 1
            stats = dict(sown["stats"])
            (spread,) = stats.pop("stream_spread")
            report.update(
                hc_stream_spread=spread,
                hc_res_row_err=jnp.max(jnp.stack(
                    jax.tree_util.tree_leaves(stats))))
        return loss, report

    return loss_fn

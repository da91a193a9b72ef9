"""Kimi-Linear: the hybrid linear-attention / latent-attention decoder in
flax, designed for mesh sharding.

The public model it expresses is **Kimi-Linear-48B-A3B-Instruct**
(moonshotai; arXiv:2510.26692): 27 layers at a hidden size of 2,304
whose *mixers* differ by a published list (``linear_attn_config``):
three **KDA** layers (Kimi Delta Attention) to one **MLA** layer.

- every block is ``x = x + Mixer_l(RMSNorm(x))``; ``x = x +
  MLP_l(RMSNorm(x))``;
- **KDA** (``ops/kda.py`` has the recurrence and its chunked form): ``q,
  k, v = W_q h, W_k h, W_v h``, each through its own depthwise causal
  convolution of 4 taps and a SiLU (``ops/conv1d.py::causal_conv1d_silu``,
  no bias); a head's ``q`` and ``k`` to unit length in float32, ``q``
  times ``head_dim^-1/2`` (``ops/kda.py::kda_scan`` with
  ``normalize_qk``: where, its path decides); the decay a channel ``g =
  -exp(A_log[head]) * softplus(W_f2 (W_f1 h) + dt_bias)``; the step
  size a head ``beta = sigmoid(W_b h)``; the gated delta rule over a
  ``[128, 128]`` state a head; ``y = W_o (sigmoid(W_g2 (W_g1 h) + b_g)
  * RMSNorm_head(o))`` (``ops/gated_norm.py::sigmoid_gated_head_rms_norm``);
- **MLA** (``ops/mla.py``) with **no query latent and no rotation**
  (``q_lora_rank`` null, ``mla_use_nope``): ``q = W_q h`` straight to
  32 heads of 128 + 64, keys and values through a 512-wide normed
  latent, the 64-wide shared key unrotated; the layer has no position
  signal but the KDA layers before it. JoyAI's kernel shapes;
- the first ``dense_layers`` blocks (1) have a dense SwiGLU MLP; the
  others JoyAI's routed layer (``models/joyai.py::MoE``: the float32
  sigmoid router over 256 experts with its selection bias, top-8
  renormalised and scaled by 2.446, SwiGLU experts of which this model
  may hold a share, ``experts_held``) plus a shared SwiGLU expert;
- a final RMSNorm and an untied head.

**What a KDA mixer keeps for the backward pass**: the six projections
of its input (``W_q h``, ``W_k h``, ``W_v h`` and the narrow ``W_f1 h``,
``W_g1 h``, ``W_b h``); the convolutions, the norms, the decay, the
recurrence and the gate's product ``W_g2 (W_g1 h) + b_g`` run again
(``_kda_core`` is a ``jax.checkpoint``), as ``latent_attention`` keeps
its latents. With ``remat`` the blocks are recomputed too
(``models/llama.py``'s switch), and a block keeps of each KDA mixer
three named arrays (``_BLOCK_KEEPS``): its gated output, and the
recurrence's forward kernel's two results, ``o`` and the state entering
every chunk (``ops/remat.py::KDA_SCAN_OUT``, ``KDA_SCAN_STATES``;
float32, 268 + 537 MB a layer at 16,384 rows of 32 heads), which are all
that the rest
of the backward pass reads of that kernel. The block's policy reaches
through ``_kda_core``'s checkpoint (a policy is handed down into a
``remat`` equation inside the one it is applied to): the kept arrays
enter that checkpoint's recomputation as its inputs, the kernel there
has no reader left and is dead code, and **the recurrence runs forward
once a layer a step** on the kernels (twice before PR 59: the pass, and
``_kda_core``'s recomputation in the block's second pass, which were
one surplus run and not two) while the convolutions, the decay and the
gate's product still run twice. The names are the kernels' forward
rule's: the XLA path (``xla_chunked``) has none and keeps its own group
states, so there the recurrence runs as before (the pass, ``_kda_core``'s
recomputation, and a group's inside ``kda_scan``). Of the latent layer
the block keeps its core's output and row statistics
(``ops/remat.py::remat_policy``), so that the latent flash forward
kernel runs once, and of a routed layer its router's float32 product,
choice, chosen scores and counts (``ops/remat.py::ROUTER_KEEPS``, 17 MB a
layer), so that the product at the highest precision, ``top_k``, the
gather and the counts' scatter-add run once; and the block's plain matmul
products, 2.9 GB over the cell's five layers: a KDA mixer's ``W_q h``,
``W_k h``, ``W_v h`` as the products leave them
(``ops/remat.py::MIXER_IN``), the stream behind either mixer
(``MIXER_STREAM``: the sum, so that the output projection has no reader
left in the second pass and the MLP's norm reads one array), and the dense
and shared MLPs' ``gate`` and ``up`` (the note ``blocks_remat_keeps``
lists every name). **The output gate**
(``ops/gated_norm.py::sigmoid_gated_head_rms_norm``, handed the mixer's mesh;
the note ``kda_gate_path``) on its kernels (``pallas``: a TPU, heads of
whole 128-lane tiles, one device or a mesh that shards the batch alone)
runs forward once a layer a step and backward once: the gated output is
what the block keeps, and ``_kda_core``'s recomputation hands the
backward kernel its operands (the kept ``o``, and ``gate`` made again),
not its result. As the XLA function (``xla``: everywhere else)
it runs forward twice, the pass and its own ``jax.checkpoint``'s
recomputation inside the backward.

It is the benchmark's sixth language model
(``kimi-linear-48b-a3b.b1-t16384`` runs layers 1-5, KDA with the dense
MLP, KDA, KDA, MLA, KDA, with one chip's share of the experts and of
the two tables). ``RMSNorm`` is ``models/llama.py``'s; ``_dense``,
``_norm``, ``_swiglu``, ``_Down``, ``_Up`` and ``MoE``
``models/joyai.py``'s, which read only the fields this config shares
with that one; the Mamba-style initialisers ``models/nemotron_h.py``'s;
the loss ``models/gpt2.py::chunked_cross_entropy``.

Program scopes (docs/observability.md): ``embed``; ``blocks`` with
``h_i/kda`` (``qkv``, ``conv``, ``qk_norm``, ``decay``, ``scan``,
``out_gate``, ``out`` beneath; ``qk_norm`` and ``scan`` are opened by
``ops/kda.py::kda_scan``, and ``qk_norm`` is a scope of its XLA path:
on the kernels a head's q and k are brought to unit length in VMEM, and
that time is inside ``scan``) in a KDA layer and ``h_i/attn``
(``q_up``, ``kv_down``, ``kv_up``, ``core``, ``out_proj``; no
``q_down``, no ``rope``) in an MLA layer, and ``h_i/mlp`` (a routed one:
``router``, ``dispatch``, ``experts``, ``combine``, ``shared``);
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

from ray_tpu.models.joyai import MoE, _dense, _Down, _norm, _swiglu, _Up
from ray_tpu.models.nemotron_h import _a_log_init, _conv_init, _dt_bias_init
from ray_tpu.ops import conv1d, gated_norm, kda, remat
from ray_tpu.ops.mla import UpProjections, latent_attention
from ray_tpu.ops.moe import held_route_share
from ray_tpu.ops.remat import (
    KDA_OUT, KDA_SCAN_OUT, KDA_SCAN_STATES, MIXER_IN, MIXER_STREAM, MLP_GATE,
    MLP_UP, ROUTER_KEEPS)
from ray_tpu.util import tracing


# what a recomputed block keeps (the module docstring): a KDA mixer's
# gated output and its recurrence's two, a routed layer's router's five,
# and the block's plain matmul products: a KDA mixer's three wide input
# projections, the stream behind either mixer (so that its output
# projection does not run again), and the dense and shared MLPs' ``gate``
# and ``up``
_BLOCK_KEEPS = (KDA_OUT, KDA_SCAN_OUT, KDA_SCAN_STATES, *ROUTER_KEEPS,
                MIXER_IN, MIXER_STREAM, MLP_GATE, MLP_UP)


@dataclass(frozen=True)
class KimiLinearConfig:
    """The keys of a ``kimi_linear`` ``config.json`` under this repo's
    names; the defaults are Kimi-Linear-48B-A3B-Instruct's."""
    vocab_size: int = 163840
    n_layer: int = 27                   # num_hidden_layers
    n_embd: int = 2304
    seq_len: int = 16384                # the rows a step is built for
    rms_eps: float = 1e-5
    # which layers, counted from 1, are MLA (full_attn_layers); the
    # others are KDA
    mla_layers: tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    # KDA (linear_attn_config)
    kda_heads: int = 32
    kda_head_dim: int = 128             # keys and values alike
    conv_kernel: int = 4                # short_conv_kernel_size
    kda_rank: int = 128                 # of the decay's and the gate's pairs
    kda_chunk: int = 64
    time_step_min: float = 0.001        # dt_bias's Mamba-style start
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # MLA
    n_head: int = 32
    kv_rank: int = 512                  # kv_lora_rank; q_lora_rank is null
    nope_dim: int = 128                 # qk_nope_head_dim
    rope_dim: int = 64                  # qk_rope_head_dim: never rotated
    v_dim: int = 128                    # v_head_dim
    rope_theta: float = 10000.0         # published; mla_use_nope: unread
    # the MLPs
    dense_layers: int = 1               # first_k_dense_replace
    dense_width: int = 9216             # intermediate_size
    num_experts: int = 256
    top_k: int = 8
    expert_width: int = 1024            # moe_intermediate_size
    shared_width: int = 1024            # num_shared_experts x that
    norm_topk_prob: bool = True         # moe_renormalize
    route_scale: float = 2.446
    # (first, count) of the experts this model holds; None: all of them
    experts_held: tuple[int, int] | None = None
    remat: bool = False                 # recompute each block in backward
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def kimi_linear_48b_a3b(**kw) -> "KimiLinearConfig":
        """moonshotai/Kimi-Linear-48B-A3B-Instruct ``config.json``: 3B
        active of 49B parameters."""
        return KimiLinearConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "KimiLinearConfig":
        """The same shape at test size: K with the dense MLP, K, K, M, K
        with routed layers of 16 experts of which 4 are held, top-3."""
        base = dict(
            vocab_size=256, n_layer=5, n_embd=64, seq_len=64,
            mla_layers=(4,), kda_heads=2, kda_head_dim=16, kda_rank=8,
            kda_chunk=16, n_head=4, kv_rank=32, nope_dim=16, rope_dim=8,
            v_dim=16, dense_width=160, num_experts=16, top_k=3,
            expert_width=32, shared_width=32, experts_held=(4, 4))
        return KimiLinearConfig(**{**base, **kw})

    def __post_init__(self):
        if not 0 <= self.dense_layers <= self.n_layer:
            raise ValueError(f"{self.dense_layers} dense layers of "
                             f"{self.n_layer}")

    def mixer(self, layer: int) -> str:
        """``M`` (MLA) or ``K`` (KDA) for ``layer``, counted from 0."""
        return "M" if layer + 1 in self.mla_layers else "K"

    @property
    def layer_kinds(self) -> str:
        """The stack's mixers in order, e.g. ``KKKMK``."""
        return "".join(self.mixer(i) for i in range(self.n_layer))

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def experts_span(self) -> tuple[int, int]:
        """(first, count) of the experts held; all of them by default."""
        return self.experts_held or (0, self.num_experts)

    @property
    def held(self) -> int:
        return self.experts_span[1]

    def layer_params(self) -> dict:
        """Parameters by part: the ``kda`` and ``mla`` mixers; the
        ``dense`` MLP; the ``routed`` one (router with its bias, shared
        expert, experts held); ``norms``, a block's two."""
        d, inner, r = self.n_embd, self.kda_inner, self.kda_rank
        h = self.n_head
        return {
            "kda": (4 * d * inner + 3 * self.conv_kernel * inner
                    + 2 * (d * r + r * inner) + 2 * inner   # dt_bias, b_g
                    + self.kda_heads + d * self.kda_heads
                    + self.kda_head_dim),
            "mla": (d * h * (self.nope_dim + self.rope_dim)
                    + d * (self.kv_rank + self.rope_dim) + self.kv_rank
                    + self.kv_rank * h * (self.nope_dim + self.v_dim)
                    + h * self.v_dim * d),
            "dense": 3 * d * self.dense_width,
            "routed": (d * self.num_experts + self.num_experts
                       + 3 * d * self.shared_width
                       + self.held * 3 * d * self.expert_width),
            "norms": 2 * d}

    def num_params(self) -> int:
        per = self.layer_params()
        mixers = sum(per["mla" if self.mixer(i) == "M" else "kda"]
                     for i in range(self.n_layer))
        return (mixers + self.n_layer * per["norms"]
                + self.dense_layers * per["dense"]
                + (self.n_layer - self.dense_layers) * per["routed"]
                + 2 * self.vocab_size * self.n_embd + self.n_embd)


def _kda_core(q, k, v, f_low, b_logit, g_low, w, *, heads: int, chunk: int,
              eps: float, mesh):
    """A KDA mixer between its input's projections and ``W_o``: (the
    gated, normed output [B, T, H*K]; the mean square of the
    recurrence's output ``o``). ``w``: the mixer's arrays that are not
    dense layers of its input."""
    b, t, inner = q.shape
    kd = inner // heads
    f32, dt = jnp.float32, q.dtype
    with jax.named_scope("conv"):
        q, k, v = (conv1d.causal_conv1d_silu(z, w[f"{n}_conv"], mesh=mesh)
                   for z, n in zip((q, k, v), "qkv"))
    with jax.named_scope("decay"):
        f = (f_low @ w["f_b"].astype(dt)).astype(f32) + w["dt_bias"]
        g = (-jnp.exp(w["A_log"])[:, None]
             * jax.nn.softplus(f).reshape(b, t, heads, kd))
        beta = jax.nn.sigmoid(b_logit.astype(f32))
    # a head's q and k go in as the convolutions left them: the
    # recurrence's path brings them to unit length (``qk_norm``, ``scan``)
    q, k, v = (z.reshape(b, t, heads, kd) for z in (q, k, v))
    o = kda.kda_scan(q, k, v, g, beta, chunk=chunk, mesh=mesh,
                     normalize_qk=True)
    out_sq = jnp.mean(jnp.square(o))
    with jax.named_scope("out_gate"):
        gate = g_low @ w["g_b"].astype(dt) + w["g_bias"].astype(dt)
        y = gated_norm.sigmoid_gated_head_rms_norm(
            o.reshape(b, t, inner), gate, w["norm"], heads, eps, mesh=mesh)
    return y, out_sq


class KDAMixer(nn.Module):
    """Kimi Delta Attention (the module docstring has the equations).
    Sows the mean square of the recurrence's output."""
    config: KimiLinearConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        heads, kd, inner = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner
        dense, normal = _dense(cfg), nn.initializers.normal(0.02)
        with jax.named_scope("qkv"):
            # as the products leave them: ``_kda_core``'s convolutions
            # read them, in both of its passes
            q, k, v = (checkpoint_name(dense(inner, name=n)(h), MIXER_IN)
                       for n in "qkv")
        with jax.named_scope("decay"):
            f_low = dense(cfg.kda_rank, name="f_a")(h)
            b_logit = dense(heads, name="b")(h)
        with jax.named_scope("out_gate"):
            g_low = dense(cfg.kda_rank, name="g_a")(h)
        conv = _conv_init(cfg)
        w = {
            **{f"{n}_conv": self.param(f"{n}_conv", conv,
                                       (cfg.conv_kernel, inner),
                                       cfg.param_dtype) for n in "qkv"},
            "f_b": self.param("f_b", normal, (cfg.kda_rank, inner),
                              cfg.param_dtype),
            "A_log": self.param("A_log", _a_log_init, (heads,), jnp.float32),
            "dt_bias": self.param("dt_bias", _dt_bias_init(cfg), (inner,),
                                  jnp.float32),
            "g_b": self.param("g_b", normal, (cfg.kda_rank, inner),
                              cfg.param_dtype),
            "g_bias": self.param("g_bias", nn.initializers.zeros, (inner,),
                                 cfg.param_dtype),
            "norm": self.param("norm", nn.initializers.ones, (kd,),
                               cfg.param_dtype)}
        y, out_sq = jax.checkpoint(functools.partial(
            _kda_core, heads=heads, chunk=cfg.kda_chunk, eps=cfg.rms_eps,
            mesh=self.mesh))(q, k, v, f_low, b_logit, g_low, w)
        self.sow("stats", "out_sq", out_sq)
        # what a recomputed block keeps of this mixer (``KimiLinear``)
        y = checkpoint_name(y, KDA_OUT)
        with jax.named_scope("out"):
            return dense(cfg.n_embd, name="out")(y)


class LatentAttention(nn.Module):
    """MLA with no query latent and no rotation (``ops/mla.py``): the
    normed input stands where the query latent would, ``q_up`` is all
    of ``W_q``."""
    config: KimiLinearConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        c_kv, k_r = _Down(cfg, cfg.kv_rank, cfg.rope_dim, name="kv_down")(h)
        q_nope, q_rope = _Up(cfg, cfg.n_embd, (("nope", cfg.nope_dim),
                                               ("rope", cfg.rope_dim)),
                             name="q_up")()
        k_nope, v = _Up(cfg, cfg.kv_rank, (("k", cfg.nope_dim),
                                           ("v", cfg.v_dim)),
                        name="kv_up")()
        o = latent_attention(
            h, c_kv, k_r, UpProjections(q_nope, q_rope, k_nope, v), None,
            n_head=cfg.n_head, mesh=self.mesh)
        return _dense(cfg)(cfg.n_embd, name="out_proj")(o)


class Block(nn.Module):
    """The layer's mixer (``kda`` or ``attn``), then its MLP (``routed``
    or the dense SwiGLU), each on the normed stream and added to it."""
    config: KimiLinearConfig
    layer: int
    mesh: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        mixer = (LatentAttention(cfg, self.mesh, name="attn")
                 if cfg.mixer(self.layer) == "M"
                 else KDAMixer(cfg, self.mesh, name="kda"))
        # the stream between the block's halves: what the MLP's norm reads
        # (a recomputed block keeps the sum and not the mixer's output
        # projection's product, which nothing reads but this add)
        x = checkpoint_name(x + mixer(_norm(cfg)(name="attn_norm")(x)),
                            MIXER_STREAM)
        mlp = (MoE(cfg, self.mesh, name="mlp")
               if self.layer >= cfg.dense_layers
               else _swiglu(cfg, cfg.dense_width, "mlp"))
        return x + mlp(_norm(cfg)(name="mlp_norm")(x))


class KimiLinear(nn.Module):
    """``__call__(tokens) -> logits`` (or the final hidden states)."""

    config: KimiLinearConfig
    mesh: Any = None

    def _constrain(self, x):
        if self.mesh is None:
            return x
        from ray_tpu.parallel.sharding import constrain
        return constrain(x, self.mesh, "batch", "seq", None)

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        tracing.note_trace(
            attn_kind="kda_mla", attn_layers=cfg.layer_kinds,
            mla_ranks=[None, cfg.kv_rank],
            mla_qk_dims=[cfg.nope_dim, cfg.rope_dim], mla_v_dim=cfg.v_dim,
            dense_layers=cfg.dense_layers, blocks_remat=cfg.remat,
            blocks_remat_keeps=remat.keeps_note(cfg.remat, _BLOCK_KEEPS))
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        with jax.named_scope("embed"):
            x = self._constrain(wte(tokens))
        # a recomputed block keeps of its KDA mixer the gated output
        # (134 MB a layer at 16,384 rows) and the recurrence's ``o`` and
        # chunk-entering states (268 + 537 MB), and of its latent layer
        # the core's output and row statistics (0.14 GB): neither
        # forward kernel runs again (the module docstring has how); and
        # of a routed layer the router's product and choice (17 MB)
        block = remat.block(Block, cfg.remat, _BLOCK_KEEPS)
        with jax.named_scope("blocks"):
            for i in range(cfg.n_layer):
                x = self._constrain(
                    block(cfg, i, self.mesh, name=f"h_{i}")(x))
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


def kimi_linear_loss_fn(model: KimiLinear, ce_chunk: int = 2048):
    """(params, batch) -> ``(loss, report)``; batch = {tokens, targets}.

    The loss is the LM loss alone (no auxiliary loss), chunked against
    the untied head. The report, which ``train/step.py`` puts beside
    the loss: ``lm_loss``; ``moe_held_route_share``, of all the routes
    of all routed layers the share that landed on the experts held,
    ``moe_absent_route_share``, the rest, and
    ``moe_load_max_over_mean``, the largest expert's routes over the
    mean in the worst layer; ``kda_out_rms``, the root mean square of
    the recurrences' output ``o`` over the KDA layers, before norm and
    gate."""
    from ray_tpu.models.gpt2 import chunked_cross_entropy
    cfg = model.config

    def loss_fn(params, batch):
        hidden, sown = model.apply({"params": params}, batch["tokens"],
                                   return_hidden=True,
                                   mutable=["moe", "stats"])
        loss = chunked_cross_entropy(
            hidden, params["lm_head"]["kernel"].T, batch["targets"],
            chunk_size=ce_chunk, mesh=model.mesh)
        report = {"lm_loss": loss}
        if "stats" in sown:
            report["kda_out_rms"] = jnp.sqrt(jnp.mean(jnp.stack(
                jax.tree_util.tree_leaves(sown["stats"]))))
        if "moe" in sown:
            load = jnp.stack(jax.tree_util.tree_leaves(sown["moe"]))
            share = held_route_share(load, cfg.experts_span)
            report.update(
                moe_held_route_share=share,
                moe_absent_route_share=1.0 - share,
                moe_load_max_over_mean=jnp.max(
                    load.max(axis=-1) / load.mean(axis=-1)))
        return loss, report

    return loss_fn

"""Granite 4.0-H: the Mamba-2 / attention hybrid under Granite's four
multipliers, every block a mixer and a dense SwiGLU MLP, in flax,
designed for mesh sharding.

The public model it expresses is **granite-4.0-h-micro** (IBM,
``model_type: granitemoehybrid``, 3B: 40 layers at a hidden size of
2,048, ``layer_types`` nine ``mamba`` to one ``attention`` in periods of
ten, the attention layers at 5, 15, 25, 35; ``num_local_experts`` 0, so
no routed layer and no router). A token's row, ``E`` the tied table:

- ``x_0 = 12 E[token]`` (``embedding_multiplier``);
- layer ``i``, ``m_r`` = 0.22 (``residual_multiplier``), RMSNorm at
  1e-5: ``x = x + m_r Mixer_i(RMSNorm(x))``, then ``x = x + m_r W_out
  (silu(g) * u)``, ``[g | u] = W_in RMSNorm(x)``, 2,048 -> 2 x 8,192 ->
  2,048 (``shared_intermediate_size``), no bias:
  ``models/phi4flash.py::MLP``, the fused ``gate_up`` under its name;
- ``mamba``: **``models/nemotron_h.py::Mamba2Mixer`` at other numbers**
  (64 heads of 64, state 128, **1 group**: every head reads the one
  ``B_t``, ``C_t``, and the gated norm runs over all 4,096 lanes; 4 taps
  with bias; **chunk 256**). There is one Mamba-2 mixer in ``ray_tpu/``;
  this config carries the fields it reads (``Mamba2Dims``);
- ``attention``: ``softmax(q k^T / 64 + causal) v``: the scale is
  ``attention_multiplier`` (0.015625), **not** ``head_dim ** -0.5``
  (0.125), 32 query over 8 key/value heads of 64, no bias and **no
  positional embedding** (``position_embedding_type: nope``;
  ``rope_theta`` is carried and read by nothing), then ``o``;
- ``logits = RMSNorm(x_L) E^T / 8`` (``logits_scaling``), the mean
  cross-entropy. **The normed stream is divided by 8 in front of
  ``chunked_cross_entropy``** (the same function, and exact in bfloat16:
  a power of two), so the loss takes no scale of its own. The table is
  read twice under two scalars: its leaf's gradient is the sum of the
  lookup's path (x 12) and the head's (/ 8).

With ``remat`` each block is recomputed in the backward pass but for
what its policy keeps by name (``_BLOCK_KEEPS``, through
``ops/remat.py::block``; the note ``blocks_remat_keeps``): the scan's
output and chunk-entering states (``SSD_SCAN_OUT``, ``SSD_SCAN_STATES``:
134 MB a layer at 8,192 rows, and ``_ssd_fwd`` runs once a layer, not
twice), the attention core's output and row statistics
(``ops/remat.py::remat_policy``), the stream between the block's two
sub-layers (``MIXER_STREAM``, 34 MB a layer: with it nothing in the
second pass reads ``out_proj``'s product or ``o``'s, and neither matmul
runs again), and, as the cell's memory decides, the MLP's ``gate_up``
product (268 MB a layer) and the three parts of the mixer's ``in_proj``
product (``IN_PROJ_PARTS``, 139 MB a layer): every layer keeps both at
8,192 rows.

``sp`` and ``tp`` meshes are refused by name: the scan runs a whole
sequence on one chip (a state passed from chip to chip is not
written), and neither the scan's kernels nor the fused ``gate_up`` have
a ``tp`` path. ``dp`` and ``fsdp`` shard the batch and need nothing.

It is the benchmark's eleventh language model
(``granite-4.0-h-micro.b1-t8192`` runs one period of ten layers with a
quarter of the table).

Program scopes (docs/observability.md): ``embed``; ``blocks`` with
``h_i/mamba`` (``in_proj``, ``conv``, ``scan``, ``gate_norm``,
``out_proj`` beneath) or ``h_i/attn`` (``qkv``, ``repeat``, ``core``,
``out``), and ``h_i/mlp``; ``loss``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.llama import RMSNorm
from ray_tpu.models.nemotron_h import Mamba2Dims, Mamba2Mixer, _dense
from ray_tpu.models.phi4flash import MLP
from ray_tpu.ops import remat
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.pallas import program
from ray_tpu.ops.remat import (
    IN_PROJ_PARTS, MIXER_STREAM, MLP_GATE_UP, SSD_SCAN_OUT, SSD_SCAN_STATES)
from ray_tpu.util import tracing

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
# What a recomputed block keeps beside its attention core's two (the
# module docstring). Every layer of the ten keeps all of it at 8,192
# rows, decided against ``peak_memory_in_bytes`` of the cell's compiled
# step (tests/test_tpu_compile_granite.py); a larger step would give
# ``gate_up``'s and ``in_proj``'s names a first layer (``{name: first}``).
_BLOCK_KEEPS = (MLP_GATE_UP, *IN_PROJ_PARTS, MIXER_STREAM, SSD_SCAN_OUT,
                SSD_SCAN_STATES)


@dataclass(frozen=True)
class GraniteHybridConfig(Mamba2Dims):
    """The keys of a ``granitemoehybrid`` ``config.json`` under this
    repo's names (``hf_config`` gives them back under the source's); the
    defaults are granite-4.0-h-micro's."""
    vocab_size: int = 100352
    layer_types: tuple[str, ...] = _PERIOD * 4
    n_embd: int = 2048
    seq_len: int = 8192                 # the rows a step is built for
    max_positions: int = 131072         # carried; no table reads it (NoPE)
    rms_eps: float = 1e-5
    # Granite's four scalars
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    # mamba: Mamba-2 (what Mamba2Dims lists)
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_expand: int = 2
    ssm_state: int = 128
    ssm_groups: int = 1                 # mamba_n_groups
    conv_kernel: int = 4
    chunk: int = 256                    # mamba_chunk_size
    time_step_min: float = 0.001        # not config.json's: Mamba-2's
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention
    n_head: int = 32
    n_kv_head: int = 8
    positions: str = "nope"
    rope_theta: float = 10000.0         # carried; read by nothing
    # the MLP of every layer, and the routed layer this model has none of
    mlp_width: int = 8192               # shared_intermediate_size
    expert_width: int = 8192            # intermediate_size
    num_experts: int = 0                # num_local_experts
    top_k: int = 0                      # num_experts_per_tok
    remat: bool = False                 # recompute each block in backward
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def granite_4_0_h_micro(**kw) -> "GraniteHybridConfig":
        """ibm-granite/granite-4.0-h-micro ``config.json``."""
        return GraniteHybridConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        """The same shape at test size: ``mamba mamba attention mamba``,
        16 Mamba heads of 8 in one group, 4 query over 2 key/value heads
        of 16 at a scale that is not ``16 ** -0.5``."""
        base = dict(
            vocab_size=256, layer_types=("mamba", "mamba", "attention",
                                         "mamba"),
            n_embd=64, seq_len=64, max_positions=64, mamba_heads=16,
            mamba_head_dim=8, ssm_state=16, chunk=16, n_head=4, n_kv_head=2,
            attention_multiplier=0.125, mlp_width=128, expert_width=128)
        return GraniteHybridConfig(**{**base, **kw})

    def __post_init__(self):
        if set(self.layer_types) - {"mamba", "attention"} \
                or not self.layer_types:
            raise ValueError(f"layer_types {self.layer_types!r}: 'mamba' "
                             "or 'attention' a layer")
        if self.positions != "nope":
            raise NotImplementedError(
                f"positions={self.positions!r}: the attention layers of "
                "this stack have no positional embedding")
        if self.num_experts or self.top_k:
            raise NotImplementedError(
                f"{self.num_experts} routed experts, {self.top_k} a token: "
                "a routed layer beside the shared MLP is not written "
                "(ROADMAP B2); this block holds the shared MLP alone")
        if self.mamba_inner != self.mamba_expand * self.n_embd:
            raise ValueError(
                f"{self.mamba_heads} heads of {self.mamba_head_dim} are "
                f"not {self.mamba_expand} x {self.n_embd}")
        if self.n_embd % self.n_head or self.n_head % self.n_kv_head:
            raise ValueError(f"{self.n_head} query and {self.n_kv_head} "
                             f"key/value heads over {self.n_embd}")

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    def hf_config(self) -> dict:
        """The source's ``config.json`` that this config stands for, key
        for key (what is fixed here and not a field is written out: the
        family's un-biased projections, its activation, its norm, the
        tied table)."""
        return {
            "attention_bias": False,
            "attention_multiplier": self.attention_multiplier,
            "embedding_multiplier": self.embedding_multiplier,
            "hidden_act": "silu", "hidden_size": self.n_embd,
            "intermediate_size": self.expert_width,
            "layer_types": list(self.layer_types),
            "logits_scaling": self.logits_scaling,
            "mamba_chunk_size": self.chunk, "mamba_conv_bias": True,
            "mamba_d_conv": self.conv_kernel,
            "mamba_d_head": self.mamba_head_dim,
            "mamba_d_state": self.ssm_state,
            "mamba_expand": self.mamba_expand,
            "mamba_n_groups": self.ssm_groups,
            "mamba_n_heads": self.mamba_heads, "mamba_proj_bias": False,
            "max_position_embeddings": self.max_positions,
            "model_type": "granitemoehybrid",
            "normalization_function": "rmsnorm",
            "num_attention_heads": self.n_head,
            "num_experts_per_tok": self.top_k,
            "num_hidden_layers": self.n_layer,
            "num_key_value_heads": self.n_kv_head,
            "num_local_experts": self.num_experts,
            "position_embedding_type": self.positions,
            "residual_multiplier": self.residual_multiplier,
            "rms_norm_eps": self.rms_eps, "rope_scaling": None,
            "rope_theta": self.rope_theta,
            "shared_intermediate_size": self.mlp_width,
            "tie_word_embeddings": True, "vocab_size": self.vocab_size}

    def layer_params(self) -> dict:
        """Parameters by part: a ``mamba`` mixer (in_proj, the
        convolution's kernel and bias, dt_bias, A_log, D, the gate
        norm, out_proj), an ``attention`` mixer (q, k, v, o), the
        ``mlp``, a block's two ``norms``."""
        d, inner, conv = self.n_embd, self.mamba_inner, self.conv_width
        kv = self.n_kv_head * self.head_dim
        return {
            "mamba": (d * (inner + conv + self.mamba_heads)
                      + (self.conv_kernel + 1) * conv + 3 * self.mamba_heads
                      + inner + inner * d),
            "attention": 2 * d * d + 2 * d * kv,
            "mlp": 3 * d * self.mlp_width, "norms": 2 * d}

    def num_params(self) -> int:
        per = self.layer_params()
        return (sum(per[k] for k in self.layer_types)
                + self.n_layer * (per["mlp"] + per["norms"])
                + self.n_embd + self.vocab_size * self.n_embd)


def _norm(cfg: GraniteHybridConfig, name: str):
    return RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype, name=name)


def _attn_fn(mesh, scale: float):
    if mesh is None:
        return lambda q, k, v: causal_attention(q, k, v, scale)
    from ray_tpu.ops.attention import make_sharded_causal_attention
    return make_sharded_causal_attention(mesh, scale=scale)


class Attention(nn.Module):
    """Grouped-query causal attention at the scale
    ``attention_multiplier``; no bias, no positions."""
    config: GraniteHybridConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        b, t, _ = h.shape
        hd, kv = cfg.head_dim, cfg.n_kv_head
        with jax.named_scope("qkv"):
            q = _dense(cfg)(cfg.n_head * hd, name="q")(h)
            k = _dense(cfg)(kv * hd, name="k")(h)
            v = _dense(cfg)(kv * hd, name="v")(h)
        # The kernels take equal head counts: the key/value heads are
        # repeated up to the query heads (4 copies at 32 over 8).
        with jax.named_scope("repeat"):
            k, v = (jnp.repeat(z.reshape(b, t, kv, hd), cfg.n_head // kv,
                               axis=2) for z in (k, v))
        with jax.named_scope("core"):
            y = _attn_fn(self.mesh, cfg.attention_multiplier)(
                q.reshape(b, t, cfg.n_head, hd), k, v)
        with jax.named_scope("out"):
            return _dense(cfg)(cfg.n_embd, name="o")(
                y.reshape(b, t, cfg.n_head * hd))


def _add_scaled(x, branch, m_r: float):
    """``x + m_r * branch`` with the product taken in float32 and
    rounded once: as a bfloat16 scalar 0.22 is 0.2197, 0.12% off the
    published multiplier in every branch and in its cotangent."""
    return x + (branch.astype(jnp.float32) * m_r).astype(x.dtype)


class Block(nn.Module):
    """``x + m_r mixer(RMSNorm(x))``, then ``x + m_r MLP(RMSNorm(x))``;
    the mixer is named by its kind, which is where the program scope
    comes from."""
    config: GraniteHybridConfig
    kind: str
    mesh: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        m_r = cfg.residual_multiplier
        h = _norm(cfg, "mixer_norm")(x)
        if self.kind == "mamba":
            y = Mamba2Mixer(cfg, self.mesh, name="mamba")(h)
        else:
            y = Attention(cfg, self.mesh, name="attn")(h)
        x = checkpoint_name(_add_scaled(x, y, m_r), MIXER_STREAM)
        return _add_scaled(
            x, MLP(cfg, name="mlp")(_norm(cfg, "mlp_norm")(x)), m_r)


class Granite(nn.Module):
    """``__call__(tokens) -> logits`` (or the final hidden states,
    normed and not yet divided by ``logits_scaling``)."""

    config: GraniteHybridConfig
    mesh: Any = None

    def _constrain(self, x):
        if self.mesh is None:
            return x
        from ray_tpu.parallel.sharding import constrain
        return constrain(x, self.mesh, "batch", "seq", None)

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        program.refuse(
            self.mesh, "Granite",
            sp="a state passed from chip to chip (the scan runs a whole "
               "sequence on one chip)",
            tp="a tp path for the scan's kernels and the fused gate_up")
        tracing.note_trace(
            attn_kind="gqa_nope_scaled", attn_scale=cfg.attention_multiplier,
            layer_pattern="".join("*" if k == "attention" else "M"
                                  for k in cfg.layer_types),
            blocks_remat=cfg.remat,
            blocks_remat_keeps=remat.keeps_note(cfg.remat, _BLOCK_KEEPS))
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        with jax.named_scope("embed"):
            x = self._constrain(wte(tokens) * cfg.embedding_multiplier)
        with jax.named_scope("blocks"):
            for i, kind in enumerate(cfg.layer_types):
                block = remat.block(Block, cfg.remat, _BLOCK_KEEPS, i)
                x = self._constrain(
                    block(cfg, kind, self.mesh, name=f"h_{i}")(x))
            x = _norm(cfg, "norm_f")(x)
        if return_hidden:
            return x
        with jax.named_scope("loss"):
            return jnp.einsum("bte,ve->btv", x,
                              wte.embedding.astype(cfg.dtype),
                              preferred_element_type=jnp.float32
                              ) / cfg.logits_scaling

    def init_params(self, rng, batch_size: int = 2):
        """Traced on a short row: no parameter's shape reads the
        sequence."""
        t = min(self.config.seq_len, 128)
        return self.init(rng, jnp.zeros((batch_size, t), jnp.int32))["params"]


def granite_loss_fn(model: Granite, ce_chunk: int = 2048):
    """(params, batch) -> ``(loss, report)``; batch = {tokens, targets}.

    The loss is the LM loss alone, chunked against the tied table, the
    normed stream divided by ``logits_scaling`` in front of it (the
    module docstring). The report, which ``train/step.py`` puts beside
    the loss: ``lm_loss``; ``mamba_out_rms``, the root mean square of
    the scans' output ``y`` (before the gate) over the Mamba layers."""
    from ray_tpu.models.gpt2 import chunked_cross_entropy
    cfg = model.config

    def loss_fn(params, batch):
        hidden, sown = model.apply({"params": params}, batch["tokens"],
                                   return_hidden=True, mutable=["stats"])
        with jax.named_scope("loss"):
            hidden = hidden / jnp.asarray(cfg.logits_scaling, hidden.dtype)
        loss = chunked_cross_entropy(
            hidden, params["wte"]["embedding"], batch["targets"],
            chunk_size=ce_chunk, mesh=model.mesh)
        report = {"lm_loss": loss}
        out_sq = jax.tree_util.tree_leaves(sown.get("stats", {}))
        if out_sq:      # a stack with a Mamba layer
            report["mamba_out_rms"] = jnp.sqrt(jnp.mean(jnp.stack(out_sq)))
        return loss, report

    return loss_fn

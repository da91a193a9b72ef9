"""Phi-4-mini-flash: the decoder-hybrid-decoder (SambaY) in flax, designed
for mesh sharding.

The public model it expresses is **Phi-4-mini-flash-reasoning**
(microsoft, ``model_type: phi4flash``, 3.8B; the SambaY architecture of
arXiv:2507.06607 with differential attention, arXiv:2410.05258): 32
layers at a hidden size of 2,560, every block

    x = x + Mixer_l(LayerNorm(x));  x = x + MLP(LayerNorm(x))

with a dense SwiGLU MLP (2,560 -> 2 x 10,240 -> 2,560, no bias) and a
mixer of one of five kinds, by the architecture's own rule
(``Phi4FlashConfig.kind``; layer ``l`` from 0, ``N`` layers, ``N % 4 ==
0``; even layers hold a Mamba-family mixer, odd layers attention):

- ``M``, ``l <= N/2`` even, **Mamba-1** (``ops/mamba1.py::mamba1_scan``):
  ``[x | z] = W_in h``; ``x = SiLU(conv4(x) + b)``; ``[delta | B | C] =
  W_x x``; ``dt = softplus(W_dt delta + b_dt)`` in float32; the
  selective scan over a ``[5120, 16]`` state whose decay differs by
  channel and by state; ``out = W_out (y * SiLU(z))``. The layer at
  ``N/2`` (``M*``) also hands on its **memory** ``m = y``, the scan's
  output before the gate;
- ``S``, ``l < N/2`` odd, **differential attention** under a window of
  512 keys (``ops/attention.py::differential_attention``): 40 query over
  20 key/value heads of 64 in adjacent pairs, two softmax maps a pair,
  subtracted with a learned ``lambda``, a 128-wide RMSNorm on the pair;
- ``F``, ``l = N/2 + 1``, the same, full causal; it also hands on its
  ``K, V``;
- ``G``, ``l >= N/2 + 2`` even, the **gated memory unit**: ``out = W_out
  (SiLU(W_in h) * m)``, token by token, ``m`` the memory of ``M*``;
- ``X``, ``l >= N/2 + 3`` odd, **cross attention**: ``q = W_q h`` alone;
  ``K, V`` are layer ``F``'s, the same tensors; the same differential
  form with this layer's own ``lambda`` and pair norm, causal.

Published, ``N`` = 32: ``(MS) x 8, M*, F, (GX) x 7``: a self-decoder, and
a cross-decoder that reads it (a gradient reaches ``F``'s ``W_qkv`` and
``M*``'s scan from every layer after them). No layer has a position
signal. LayerNorm has scale and bias (``models/gpt2.py``'s); a final
LayerNorm; logits against the **tied** table (``models/zaya.py``'s path
through ``models/gpt2.py::chunked_cross_entropy``).

**What crosses blocks**: a block hands on, beside ``x``, the memory and
the ``(K, V)`` pair (None before the layers that make them), as ZAYA's
blocks hand on the router's state. With ``remat`` each block is
recomputed in the backward pass and those are kept block outputs, as
are an attention core's output and row statistics
(``ops/remat.py::remat_policy``: the flash forward kernel runs once
a layer) and the MLP's ``gate_up`` product (``[T, 2 x 10,240]``, 168 MB
a layer at 4,096 rows: the block's dearest matmul does not run twice;
``down``'s product is added to the stream as it is, so nothing in the
backward pass reads it and it needs no name), the mixer's input
projection's product (``ops/remat.py::MIXER_IN``; an attention layer's q,
k, v as projected, ``ATTN_Q``, ``ATTN_K``, ``ATTN_V``: a third of the
cores' operands as ``repeat`` writes them out), the stream behind the
mixer (``MIXER_STREAM``: the mixer's output projection has no reader
left) and the Mamba-1 scan's ``y`` and entering states (the kernels'
forward rule's names: the forward kernel runs once a layer); the note
``blocks_remat_keeps`` lists them.

It is the benchmark's seventh language model
(``phi-4-mini-flash-reasoning.b1-t4096`` runs the rule at ``N`` = 8,
``M S M S M* F G X``, with an eighth of the table). The Mamba-style
initialisers are ``models/nemotron_h.py``'s.

Program scopes (docs/observability.md): ``embed``; ``blocks`` with
``h_i/mamba`` (``in_proj``, ``conv``, ``x_proj``, ``dt``, ``scan``,
``gate``, ``out_proj`` beneath), ``h_i/attn`` (``qkv`` or ``q``,
``repeat``, then ``window`` in an ``S`` layer, ``core`` in ``F``,
``cross`` in ``X``, ``diff``, ``out``), ``h_i/gmu`` (``in_proj``,
``gate``, ``out_proj``) and ``h_i/mlp``; ``loss``.
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

from ray_tpu.models.nemotron_h import _conv_init, _dt_bias_init
from ray_tpu.ops import conv1d, mamba1, remat
from ray_tpu.ops.attention import differential_attention
from ray_tpu.ops.remat import (
    ATTN_K, ATTN_Q, ATTN_V, MAMBA1_SCAN_OUT, MAMBA1_SCAN_STATES, MIXER_IN,
    MIXER_STREAM, MLP_GATE_UP)
from ray_tpu.util import tracing

# what a recomputed block keeps (the module docstring): its MLP's
# ``gate_up`` product, its mixer's input projection's product (an
# attention layer's ``q``, ``k``, ``v``), the stream behind the mixer
# (so that its output projection does not run again), and the Mamba-1
# scan's forward kernel's two results. Not the differential combination's
# result: kept, ``W_o``'s backward gave back what the second pass saved
# (PERF.md section 6, PR 70)
_BLOCK_KEEPS = (MLP_GATE_UP, MIXER_IN, MIXER_STREAM, ATTN_Q, ATTN_K, ATTN_V,
                MAMBA1_SCAN_OUT, MAMBA1_SCAN_STATES)


@dataclass(frozen=True)
class Phi4FlashConfig:
    """The keys of a ``phi4flash`` ``config.json`` under this repo's
    names; the defaults are Phi-4-mini-flash-reasoning's."""
    vocab_size: int = 200064
    n_layer: int = 32                   # num_hidden_layers
    n_embd: int = 2560
    seq_len: int = 4096                 # the rows a step is built for
    ln_eps: float = 1e-5                # layer_norm_eps
    mb_per_layer: int = 2               # a Mamba-family mixer every 2nd
    # differential attention
    n_head: int = 40
    n_kv_head: int = 20
    head_dim: int = 64
    window: int = 512                   # sliding_window, the S layers'
    lambda_std: float = 0.1             # the four lambda vectors' start
    # the MLP
    mlp_width: int = 10240              # intermediate_size
    # Mamba-1 (none of it in config.json: the family's convention)
    mamba_inner: int = 5120             # expand 2
    ssm_state: int = 16
    conv_kernel: int = 4
    dt_rank: int = 160                  # ceil(n_embd / 16)
    ssm_chunk: int = 4                  # rows a chunk of the scan's XLA path
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    remat: bool = False                 # recompute each block in backward
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def phi_4_mini_flash_reasoning(**kw) -> "Phi4FlashConfig":
        """microsoft/Phi-4-mini-flash-reasoning ``config.json``."""
        return Phi4FlashConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "Phi4FlashConfig":
        """The same shape at test size: ``M S M S M* F G X``, 8 query
        over 4 key/value heads of 8, a window of 8 keys."""
        base = dict(
            vocab_size=256, n_layer=8, n_embd=32, seq_len=32, n_head=8,
            n_kv_head=4, head_dim=8, window=8, mlp_width=64, mamba_inner=64,
            ssm_state=4, dt_rank=2, ssm_chunk=8)
        return Phi4FlashConfig(**{**base, **kw})

    def __post_init__(self):
        if self.n_layer % 4 or self.mb_per_layer != 2:
            raise ValueError(
                f"{self.n_layer} layers, a Mamba-family mixer every "
                f"{self.mb_per_layer}: the rule needs whole (M, attention) "
                "pairs in both halves")
        if (self.n_head % 2 or self.n_kv_head % 2
                or self.n_head % self.n_kv_head):
            raise ValueError(f"{self.n_head} query and {self.n_kv_head} "
                             "key/value heads do not pair")

    @property
    def memory_layer(self) -> int:
        """``M*``: the Mamba layer whose scan the gated memory units
        read."""
        return self.n_layer // 2

    @property
    def kv_layer(self) -> int:
        """``F``: the full layer whose K and V the cross-decoder reads."""
        return self.n_layer // 2 + 1

    def kind(self, layer: int) -> str:
        """``M``, ``S``, ``F``, ``G`` or ``X`` for ``layer`` from 0."""
        half = self.n_layer // 2
        if layer % self.mb_per_layer == 0:
            return "M" if layer <= half else "G"
        if layer < half:
            return "S"
        return "F" if layer == half + 1 else "X"

    @property
    def layer_kinds(self) -> str:
        """The stack's mixers in order, e.g. ``MSMSMFGX`` (``M*`` reads
        ``M``: ``memory_layer`` says which)."""
        return "".join(self.kind(i) for i in range(self.n_layer))

    def lambda_init(self, layer: int) -> float:
        """``0.8 - 0.6 exp(-0.3 l)``, the depth counted from 0."""
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    def layer_params(self) -> dict:
        """Parameters by part: a mixer of each kind (``S`` and ``F``
        are alike), the ``mlp``, a block's two ``norms``."""
        d, inner, n, r = (self.n_embd, self.mamba_inner, self.ssm_state,
                          self.dt_rank)
        q, kv = self.n_head * self.head_dim, self.n_kv_head * self.head_dim
        diff = 4 * self.head_dim + 2 * self.head_dim    # lambdas, pair norm
        attn = (d + 1) * (q + 2 * kv) + diff + (q + 1) * d
        return {
            "M": (d * 2 * inner + (self.conv_kernel + 1) * inner
                  + inner * (r + 2 * n) + (r + 1) * inner + inner * n + inner
                  + inner * d),
            "S": attn, "F": attn,
            "G": 2 * d * inner,
            "X": (d + 1) * q + diff + (q + 1) * d,
            "mlp": 3 * d * self.mlp_width,
            "norms": 4 * d}

    def num_params(self) -> int:
        per = self.layer_params()
        return (sum(per[k] for k in self.layer_kinds)
                + self.n_layer * (per["mlp"] + per["norms"])
                + 2 * self.n_embd + self.vocab_size * self.n_embd)


def _dense(cfg: Phi4FlashConfig, bias: bool = False):
    return partial(nn.Dense, use_bias=bias, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype,
                   kernel_init=nn.initializers.normal(0.02))


def _norm(cfg: Phi4FlashConfig):
    return partial(nn.LayerNorm, epsilon=cfg.ln_eps, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype)


def _a_log_init(key, shape, dtype):
    """Mamba-1's S4D-real start: ``A[c, n] = -(n + 1)``."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)),
                            shape)


def _lambda(vec: dict, lam_init: float):
    """``exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init``, float32."""
    return (jnp.exp(jnp.sum(vec["q1"] * vec["k1"]))
            - jnp.exp(jnp.sum(vec["q2"] * vec["k2"])) + lam_init)


class _Conv(nn.Module):
    """``conv1d``: the depthwise causal convolution's [K, C] kernel and
    bias, then SiLU."""
    config: Phi4FlashConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        w = self.param("kernel", _conv_init(cfg),
                       (cfg.conv_kernel, cfg.mamba_inner), cfg.param_dtype)
        b = self.param("bias", _conv_init(cfg), (cfg.mamba_inner,),
                       cfg.param_dtype)
        return conv1d.causal_conv1d_silu(x, w, b, mesh=self.mesh)


class _DtProj(nn.Module):
    """``dt_proj``: 160 -> 5,120 with Mamba's bias; float32 out."""
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, delta):
        cfg = self.config
        bound = cfg.dt_rank ** -0.5
        w = self.param(
            "kernel", lambda k, s, d: jax.random.uniform(k, s, d, -bound,
                                                         bound),
            (cfg.dt_rank, cfg.mamba_inner), cfg.param_dtype)
        b = self.param("bias", _dt_bias_init(cfg), (cfg.mamba_inner,),
                       jnp.float32)
        return jnp.einsum("btr,rc->btc", delta, w.astype(cfg.dtype),
                          preferred_element_type=jnp.float32) + b


class Mamba(nn.Module):
    """The Mamba-1 mixer: (its output, the scan's output ``y`` before
    the gate, in the compute dtype). Sows the mean square of ``y``."""
    config: Phi4FlashConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        inner, n, r = cfg.mamba_inner, cfg.ssm_state, cfg.dt_rank
        f32 = jnp.float32
        x, z = jnp.split(checkpoint_name(
            _dense(cfg)(2 * inner, name="in_proj")(h), MIXER_IN), 2, -1)
        with jax.named_scope("conv"):
            x = _Conv(cfg, self.mesh, name="conv1d")(x)
        dbc = _dense(cfg)(r + 2 * n, name="x_proj")(x)
        with jax.named_scope("dt"):
            dt = jax.nn.softplus(_DtProj(cfg, name="dt_proj")(dbc[..., :r]))
        a_log = self.param("A_log", _a_log_init, (inner, n), f32)
        skip = self.param("D", nn.initializers.ones, (inner,), f32)
        with jax.named_scope("scan"):
            y = mamba1.mamba1_scan(
                x, dt, -jnp.exp(a_log), dbc[..., r:r + n], dbc[..., r + n:],
                skip, chunk=cfg.ssm_chunk, mesh=self.mesh)
        self.sow("stats", "out_sq", jnp.mean(jnp.square(y)))
        with jax.named_scope("gate"):
            gated = (y * jax.nn.silu(z.astype(f32))).astype(cfg.dtype)
        return (_dense(cfg)(cfg.n_embd, name="out_proj")(gated),
                y.astype(cfg.dtype))


class GatedMemoryUnit(nn.Module):
    """``W_out (SiLU(W_in h) * m)``: ``m`` the memory of ``M*``."""
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, h, memory):
        cfg = self.config
        a = checkpoint_name(
            _dense(cfg)(cfg.mamba_inner, name="in_proj")(h), MIXER_IN)
        with jax.named_scope("gate"):
            gated = jax.nn.silu(a) * memory
        return _dense(cfg)(cfg.n_embd, name="out_proj")(gated)


class _Projection(nn.Module):
    """One [d, sum(widths)] kernel with its bias, applied as a matmul a
    part: each part comes out as a matmul wrote it
    (``models/gpt2.py::CausalSelfAttention`` has what slicing one
    output costs)."""
    config: Phi4FlashConfig
    widths: tuple[int, ...]

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        total = sum(self.widths)
        w = self.param("kernel", nn.initializers.normal(0.02),
                       (cfg.n_embd, total), cfg.param_dtype).astype(cfg.dtype)
        b = self.param("bias", nn.initializers.zeros, (total,),
                       cfg.param_dtype).astype(cfg.dtype)
        out, at = [], 0
        for width in self.widths:
            out.append(h @ w[:, at:at + width] + b[at:at + width])
            at += width
        return out


class DiffAttention(nn.Module):
    """Differential attention: an ``S`` or ``F`` layer makes ``q, k, v``
    from its input and returns (its output, ``(k, v)``); an ``X`` layer
    (``cross``) makes ``q`` alone and reads the ``kv`` it is given."""
    config: Phi4FlashConfig
    layer: int
    mesh: Any = None

    @nn.compact
    def __call__(self, h, kv=None):
        cfg = self.config
        b, t, _ = h.shape
        hd = cfg.head_dim
        kind = cfg.kind(self.layer)
        q_w, kv_w = cfg.n_head * hd, cfg.n_kv_head * hd
        h = h.astype(cfg.dtype)
        # the products as a matmul wrote them (``repeat`` writes the core's
        # operands out of them again in a recomputed block's second pass:
        # kept as the core reads them they are three times the bytes)
        if kind == "X":
            (q,) = _Projection(cfg, (q_w,), name="q")(h)
            q = checkpoint_name(q, ATTN_Q)
            k, v = kv
        else:
            q, k, v = (checkpoint_name(z, n) for z, n in zip(
                _Projection(cfg, (q_w, kv_w, kv_w), name="qkv")(h),
                (ATTN_Q, ATTN_K, ATTN_V)))
            k, v = (z.reshape(b, t, cfg.n_kv_head, hd) for z in (k, v))
        lam_vec = {n: self.param(f"lambda_{n}",
                                 nn.initializers.normal(cfg.lambda_std),
                                 (hd,), jnp.float32)
                   for n in ("q1", "k1", "q2", "k2")}
        subln = self.param("subln", nn.initializers.ones, (2 * hd,),
                           cfg.param_dtype)
        lam_init = cfg.lambda_init(self.layer)
        with jax.named_scope("diff"):
            lam = _lambda(lam_vec, lam_init)
        o = differential_attention(
            q.reshape(b, t, cfg.n_head, hd), k, v, lam, lam_init, subln,
            window=cfg.window if kind == "S" else None, mesh=self.mesh,
            eps=cfg.ln_eps,
            scope={"S": "window", "F": "core", "X": "cross"}[kind])
        return _dense(cfg, bias=True)(cfg.n_embd, name="out")(o), (k, v)


class MLP(nn.Module):
    """``W_d (SiLU(g) * u)``, ``[g | u] = W_gu h``; no bias."""
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        # what a recomputed block keeps of this MLP (``Phi4Flash``)
        gu = checkpoint_name(
            _dense(cfg)(2 * cfg.mlp_width, name="gate_up")(h), MLP_GATE_UP)
        g, u = jnp.split(gu, 2, -1)
        return _dense(cfg)(cfg.n_embd, name="down")(jax.nn.silu(g) * u)


class Block(nn.Module):
    """(x, the memory, the (K, V) pair) -> the same three: the layer's
    mixer, which may read or make the second and third, then the MLP,
    each on the normed stream and added to it."""
    config: Phi4FlashConfig
    layer: int
    mesh: Any = None

    @nn.compact
    def __call__(self, x, memory, kv):
        cfg = self.config
        kind = cfg.kind(self.layer)
        h = _norm(cfg)(name="ln_1")(x)
        if kind == "M":
            y, m = Mamba(cfg, self.mesh, name="mamba")(h)
            if self.layer == cfg.memory_layer:
                memory = m
        elif kind == "G":
            y = GatedMemoryUnit(cfg, name="gmu")(h, memory)
        else:
            y, made = DiffAttention(cfg, self.layer, self.mesh, name="attn")(
                h, kv)
            if self.layer == cfg.kv_layer:
                kv = made
        # the stream between the block's halves: what ``ln_2`` reads (a
        # recomputed block keeps the sum, not the mixer's last product,
        # which nothing reads but this add)
        x = checkpoint_name(x + y, MIXER_STREAM)
        return (x + MLP(cfg, name="mlp")(_norm(cfg)(name="ln_2")(x)),
                memory, kv)


class Phi4Flash(nn.Module):
    """``__call__(tokens) -> logits`` (or the final hidden states)."""

    config: Phi4FlashConfig
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
            attn_kind="differential", layer_pattern=cfg.layer_kinds,
            attn_window=cfg.window, ssm_kind="mamba1",
            ssm_tokens=tokens.size, ssm_inner=cfg.mamba_inner,
            ssm_state=cfg.ssm_state, ssm_dt_rank=cfg.dt_rank,
            yoco_memory_layer=cfg.memory_layer, yoco_kv_layer=cfg.kv_layer,
            blocks_remat=cfg.remat,
            blocks_remat_keeps=remat.keeps_note(cfg.remat, _BLOCK_KEEPS))
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        with jax.named_scope("embed"):
            x = self._constrain(wte(tokens))
        # a recomputed block keeps its attention core's output and row
        # statistics (42 MB a layer at 4,096 rows), as models/laguna.py,
        # and ``_BLOCK_KEEPS`` (its MLP's gate_up product, 168 MB a layer,
        # and 0.1 GB a layer of its mixer's)
        block = remat.block(Block, cfg.remat, _BLOCK_KEEPS)
        memory = kv = None
        with jax.named_scope("blocks"):
            for i in range(cfg.n_layer):
                x, memory, kv = block(cfg, i, self.mesh, name=f"h_{i}")(
                    x, memory, kv)
                x = self._constrain(x)
            x = _norm(cfg)(name="ln_f")(x)
        if return_hidden:
            return x
        with jax.named_scope("loss"):
            return jnp.einsum("bte,ve->btv", x,
                              wte.embedding.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)

    def init_params(self, rng, batch_size: int = 2):
        """Traced on a short row: no parameter's shape reads the
        sequence."""
        t = min(self.config.seq_len, 128)
        return self.init(rng, jnp.zeros((batch_size, t), jnp.int32))["params"]


def phi4flash_loss_fn(model: Phi4Flash, ce_chunk: int = 2048):
    """(params, batch) -> ``(loss, report)``; batch = {tokens, targets}.

    The loss is the LM loss alone, chunked against the tied table. The
    report, which ``train/step.py`` puts beside the loss: ``lm_loss``;
    ``mamba_out_rms``, the root mean square of the scans' output ``y``
    (before the gate) over the Mamba layers."""
    from ray_tpu.models.gpt2 import chunked_cross_entropy

    def loss_fn(params, batch):
        hidden, sown = model.apply({"params": params}, batch["tokens"],
                                   return_hidden=True, mutable=["stats"])
        loss = chunked_cross_entropy(
            hidden, params["wte"]["embedding"], batch["targets"],
            chunk_size=ce_chunk, mesh=model.mesh)
        return loss, {
            "lm_loss": loss,
            "mamba_out_rms": jnp.sqrt(jnp.mean(jnp.stack(
                jax.tree_util.tree_leaves(sown["stats"]))))}

    return loss_fn

"""Laguna: the window/full decoder whose layers differ in head count,
in flax, designed for mesh sharding.

The public model it expresses is **Laguna-XS.2** (poolside, "33B-A3B":
40 layers at a hidden size of 2,048, 262,144 positions). Its
``config.json`` gives three lists, one entry a layer, and this file
reads each layer's entry from them and derives nothing from a period:

- ``layer_types``: ``full_attention`` or ``sliding_attention`` (one full
  layer, then three sliding ones, ten times);
- ``num_attention_heads_per_layer``: **48 query heads on a full layer,
  64 on a sliding one**, over 8 key/value heads of 128 on both, so
  ``W_q``, ``W_o``, the gate and the copies of K and V (6 and 8) have
  another shape from one layer of the stack to the next;
- ``mlp_layer_types``: layer 0 ``dense`` (SwiGLU at 8,192), the others
  ``sparse``.

Every block is ``x = x + Attn_l(RMSNorm(x))``; ``x = x +
MLP_l(RMSNorm(x))``, no bias anywhere; then a final RMSNorm and an
**untied** head. What sets it apart from ``models/smallthinker.py``,
the other window/global stack:

- **two position tables in one stack**: a sliding layer rotates all
  128 lanes of q and k in halves at theta 10,000 (``rope_freqs``); a
  full layer rotates **lanes 0-63** in halves under **YaRN**
  (``models/llama.py::yarn_freqs``: theta 500,000, factor 64, an
  original length of 4,096, ``beta_fast`` 64, ``beta_slow`` 1), cos and
  sin times the ``attention_factor`` 1.4158883, and passes lanes 64-127
  through. Both tables are made once a model and each layer is handed
  its own;
- **a gate a head**: ``g = sigmoid(h W_g)``, ``W_g`` 2048 -> H_l, one
  value a head and a token from the block's normed input, times the
  core's output before ``W_o`` (arXiv:2505.06708's head-wise form;
  ``config.json`` says ``gating: true`` and no more);
- a sliding layer's row ``t`` sees keys ``t - 512 < j <= t``
  (``ops/attention.py::causal_attention(window=...)``, whose flash
  kernels skip the blocks outside the band);
- the routed layers are DeepSeek-V3's as ``models/joyai.py::MoE`` runs
  them (``ops/moe.py::routed_ffn``: the float32 sigmoid router over 256
  experts with its selection bias, top-8 renormalised and scaled by
  2.5, SwiGLU experts of width 512, of which this model may hold a
  share, ``experts_held``) plus a shared SwiGLU expert at 512 on every
  token.

With ``remat`` each block is recomputed in the backward pass
(``nn.remat``, as ``models/kimi_linear.py``): kept whole, the step at
16,384 rows would hold eight copies of K and V, the gated cores and a
32,768-row slab of sorted routes a layer beside everything below.
What a recomputed block does keep, by name (``_BLOCK_KEEPS`` and
``ops/remat.py::remat_policy``; the note ``blocks_remat_keeps``
lists them), is every matmul's product its backward pass reads and the
flash forward's results, so that no matmul of a block and no forward
kernel runs twice (bytes a layer at 16,384 rows):

- its core's output and row statistics (0.20 GB a full layer, 0.27 a
  sliding one);
- a routed layer's router: the float32 product ``x W_r`` in front of
  the sigmoid, the chosen ``experts``, their scores and the routes each
  expert received (``ops/remat.py::ROUTER_KEEPS``, 17 MB): the second
  pass makes the sigmoid again from the kept product and neither the
  product at the highest precision, ``top_k``, the gather nor the
  scatter-add of the counts;
- ``W_o``'s product (67 MB): the stream between the two sub-layers is
  then one add;
- q, k and v (0.20 | 0.27 GB and 2 x 34 MB), so that the three
  products run once. **A full layer keeps q and k as the rotation left
  them**: the rotation's backward is linear in the cotangent and reads
  the angles alone, so the float32 rotation is not made again either.
  **A sliding layer keeps them as the products left them** and rotates
  again (3.0 ms a layer): with nothing of the rotation left in its
  backward pass, XLA lays that layer's backward rotation out with a
  half head's 64 lanes minor, half of every 128-lane tile empty, and
  it takes 8.7 ms where it took 4.1 (PERF.md section 6, PR 66). The
  copies of K and V (``repeat``) are made again: they are what
  GQA-native kernels would spare, not a name;
- the ``gate`` and ``up`` products of layer 0's dense MLP (2 x 0.27
  GB) and of each shared expert (2 x 17 MB), which ``models/llama.py::
  SwiGLU`` names.

The head gate's product (2048 -> 48 | 64, 0.1 ms a layer) and its
multiply, whose output would be as large as q, are made again. The
list is every layer's: the compiled step asks for 13.75 GB of the
chip's 16.91 with it (``tests/test_tpu_compile_laguna.py``).

It is the benchmark's eighth language model
(``laguna-xs.2.b1-t16384`` runs layers 0-4, ``F S S S F``, with one
chip's share of the experts, 32 of 256, and of the two tables).
``RMSNorm``, ``rope_freqs``, ``yarn_freqs`` and ``apply_rope_half`` are
``models/llama.py``'s; ``_dense``, ``_norm``, ``_swiglu`` and ``MoE``
``models/joyai.py``'s, which read only the fields this config shares
with that one.

Program scopes (docs/observability.md): ``embed``; ``blocks`` with
``h_i/attn`` (``qkv``, ``rope``, ``repeat``, the kernel's call under
``window`` in a sliding layer and under ``core`` in a full one,
``gate``, ``out``) and ``h_i/mlp`` (layer 0's dense MLP by itself; a
routed one: ``router``, ``dispatch``, ``experts``, ``combine``,
``shared``); ``loss``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.joyai import MoE, _dense, _norm, _swiglu
from ray_tpu.models.llama import apply_rope_half, rope_freqs, yarn_freqs
from ray_tpu.ops import remat
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.remat import (
    ATTN_K, ATTN_PROJ, ATTN_Q, ATTN_V, MLP_GATE, MLP_UP, ROUTER_KEEPS)
from ray_tpu.ops.moe import held_route_share
from ray_tpu.util import tracing

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"

# what a recomputed block keeps beside its core's output and row
# statistics, dearest millisecond a byte first (the module docstring;
# q and k as rotated in a full layer, as projected in a sliding one)
_BLOCK_KEEPS = (*ROUTER_KEEPS, ATTN_PROJ, ATTN_Q, ATTN_K, ATTN_V,
                MLP_GATE, MLP_UP)


@dataclass(frozen=True)
class LagunaConfig:
    """The keys of a ``laguna`` ``config.json`` under this repo's names;
    the defaults are Laguna-XS.2's. The three per-layer lists are the
    published ones whole; a model of ``n_layer`` layers runs their first
    ``n_layer`` entries."""
    vocab_size: int = 100352
    n_layer: int = 40                   # num_hidden_layers
    n_embd: int = 2048
    seq_len: int = 262144               # max_position_embeddings
    rms_eps: float = 1e-6
    layer_types: tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING) * 10
    heads_per_layer: tuple[int, ...] = (48, 64, 64, 64) * 10
    mlp_layer_types: tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    n_kv_head: int = 8
    head_dim: int = 128
    window: int = 512                   # sliding_window
    # rope_parameters.sliding_attention: every lane, rope_type default
    sliding_theta: float = 10_000.0
    # rope_parameters.full_attention: rope_type yarn on the first
    # ``full_rotary`` of a head's lanes
    full_theta: float = 500_000.0
    full_rotary: float = 0.5            # partial_rotary_factor
    yarn_factor: float = 64.0
    yarn_original_len: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    # None: 0.1 ln(factor) + 1, which is what the published number is
    yarn_attention_factor: float | None = 1.4158883083359672
    # the MLPs
    dense_width: int = 8192             # intermediate_size
    num_experts: int = 256              # the router's width
    top_k: int = 8                      # num_experts_per_tok
    expert_width: int = 512             # moe_intermediate_size
    shared_width: int = 512             # shared_expert_intermediate_size
    norm_topk_prob: bool = True
    route_scale: float = 2.5            # moe_routed_scaling_factor
    # (first, count) of the experts this model holds, as one chip of an
    # expert-parallel deployment does; None: all of them
    experts_held: tuple[int, int] | None = None
    remat: bool = False                 # recompute each block in backward
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def laguna_xs_2(**kw) -> "LagunaConfig":
        """poolside/Laguna-XS.2 ``config.json``: 3B active of 33.4B."""
        return LagunaConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "LagunaConfig":
        """The same shape at test size: ``F S S S F`` with layer 0's MLP
        dense, 6 and 8 query heads over 2 key/value heads of 16 (groups
        of 3 and 4), a window of 24 of 64 rows, YaRN by 4 from an
        original length of 16 on 8 of the 16 lanes, 16 experts of which
        4 are held, top-3."""
        base = dict(
            vocab_size=256, n_layer=5, n_embd=64, seq_len=64,
            layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
            heads_per_layer=(6, 8, 8, 8, 6),
            mlp_layer_types=(DENSE, SPARSE, SPARSE, SPARSE, SPARSE),
            n_kv_head=2, head_dim=16, window=24, full_theta=10000.0,
            sliding_theta=100.0, yarn_factor=4.0, yarn_original_len=16,
            yarn_beta_fast=4.0, yarn_attention_factor=None,
            dense_width=160, num_experts=16, top_k=3, expert_width=32,
            shared_width=32, experts_held=(4, 4))
        return LagunaConfig(**{**base, **kw})

    def __post_init__(self):
        for name in ("layer_types", "heads_per_layer", "mlp_layer_types"):
            if len(getattr(self, name)) < self.n_layer:
                raise ValueError(f"{name} has {len(getattr(self, name))} "
                                 f"entries for {self.n_layer} layers")
        for i in range(self.n_layer):
            if self.layer_types[i] not in (FULL, SLIDING):
                raise ValueError(f"layer {i}: {self.layer_types[i]!r}")
            if self.mlp_layer_types[i] not in (DENSE, SPARSE):
                raise ValueError(f"layer {i}: {self.mlp_layer_types[i]!r}")
            if self.heads_per_layer[i] % self.n_kv_head:
                raise ValueError(
                    f"layer {i}: {self.heads_per_layer[i]} query heads "
                    f"over {self.n_kv_head} key/value heads")

    def sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == SLIDING

    def heads(self, layer: int) -> int:
        return self.heads_per_layer[layer]

    def routed(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == SPARSE

    @property
    def routed_layers(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_layer) if self.routed(i))

    @property
    def layer_kinds(self) -> str:
        """A letter a layer: ``F`` full, ``S`` sliding."""
        return "".join("S" if self.sliding(i) else "F"
                       for i in range(self.n_layer))

    @property
    def rotated_lanes(self) -> int:
        """Lanes of a head that a full layer rotates (the first ones)."""
        return int(self.head_dim * self.full_rotary)

    @property
    def experts_span(self) -> tuple[int, int]:
        """(first, count) of the experts held; all of them by default."""
        return self.experts_held or (0, self.num_experts)

    @property
    def held(self) -> int:
        return self.experts_span[1]

    def layer_params(self, layer: int) -> dict:
        """Parameters of ``layer`` by part: ``attn`` (W_q, W_k, W_v, W_o
        and the gate at the layer's head count), the ``dense`` MLP or
        the ``router`` (its bias counts), the ``shared`` expert and the
        ``experts`` held, ``rest`` (two norms)."""
        d, hd, h = self.n_embd, self.head_dim, self.heads(layer)
        parts = {"attn": 2 * d * h * hd + 2 * d * self.n_kv_head * hd + d * h,
                 "rest": 2 * d}
        if not self.routed(layer):
            return {**parts, "dense": 3 * d * self.dense_width}
        return {**parts,
                "router": d * self.num_experts + self.num_experts,
                "shared": 3 * d * self.shared_width,
                "experts": self.held * 3 * d * self.expert_width}

    def num_params(self) -> int:
        return (sum(sum(self.layer_params(i).values())
                    for i in range(self.n_layer))
                + 2 * self.vocab_size * self.n_embd + self.n_embd)


def _attn_fn(cfg: LagunaConfig, mesh, sliding: bool):
    """The layer's attention over equal-width q, k, v: the window in a
    sliding layer, every key up to the row in a full one."""
    window = cfg.window if sliding else None
    if mesh is None:
        return lambda q, k, v: causal_attention(q, k, v, window=window)
    from ray_tpu.ops.attention import make_sharded_causal_attention
    return make_sharded_causal_attention(mesh, window=window)


class Attention(nn.Module):
    """GQA at this layer's head count under this layer's mask and
    positions, its core's output gated a head: ``rope`` is ``(angles
    [T, r/2], amplitude)`` over the first ``r`` lanes."""
    config: LagunaConfig
    heads: int
    sliding: bool
    mesh: Any = None

    @nn.compact
    def __call__(self, h, angles, amplitude: float):
        cfg = self.config
        b, t, _ = h.shape
        hd, heads = cfg.head_dim, self.heads
        with jax.named_scope("qkv"):
            q = _dense(cfg)(heads * hd, name="q")(h)
            k = _dense(cfg)(cfg.n_kv_head * hd, name="k")(h)
            v = _dense(cfg)(cfg.n_kv_head * hd, name="v")(h)
        q = q.reshape(b, t, heads, hd)
        k = k.reshape(b, t, cfg.n_kv_head, hd)
        v = checkpoint_name(v.reshape(b, t, cfg.n_kv_head, hd), ATTN_V)
        if self.sliding:
            # named in front of the rotation (the module docstring)
            q, k = checkpoint_name(q, ATTN_Q), checkpoint_name(k, ATTN_K)
        with jax.named_scope("rope"):
            q = _rotate(q, angles[:t], amplitude)
            k = _rotate(k, angles[:t], amplitude)
        if not self.sliding:
            # named behind the rotation: its backward is linear in the
            # cotangent and reads the angles alone, so a block that
            # keeps these makes neither the products nor the float32
            # rotation again
            q, k = checkpoint_name(q, ATTN_Q), checkpoint_name(k, ATTN_K)
        rep = heads // cfg.n_kv_head
        if rep > 1:
            # The equal-width kernels want as many key/value heads as
            # query heads: each is written ``rep`` times to HBM (and
            # its cotangent summed over the copies), 6 times in a full
            # layer and 8 in a sliding one. GQA-native K/V in the
            # kernel is ROADMAP B2's; this scope shows what the copies
            # cost.
            with jax.named_scope("repeat"):
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
        with jax.named_scope("window" if self.sliding else "core"):
            o = _attn_fn(cfg, self.mesh, self.sliding)(q, k, v)
        if self.sliding:
            # What a whole window hands on (``models/smallthinker.py``
            # has why): the mean square of the rows that see ``window``
            # keys, before the gate.
            whole = o[:, min(cfg.window, t) - 1:].astype(jnp.float32)
            self.sow("stats", "out_sq", jnp.mean(jnp.square(whole)))
        with jax.named_scope("gate"):
            g = jax.nn.sigmoid(
                _dense(cfg)(heads, name="g")(h).astype(jnp.float32))
            o = o * g[..., None].astype(o.dtype)
        with jax.named_scope("out"):
            return checkpoint_name(_dense(cfg)(cfg.n_embd, name="out")(
                o.reshape(b, t, heads * hd)), ATTN_PROJ)


def _rotate(x, angles, amplitude: float):
    """The first ``2 * angles.shape[-1]`` lanes of x [B, T, H, D]
    rotated in halves, times ``amplitude``; the others pass through.
    The rotation runs in float32 and is written back in x's type (one
    fused pass either way): cos, sin and above all the amplitude
    rounded to bfloat16 first (1.4158883 -> 1.4140625, 0.13% low on q
    and on k) would be a scale on every score of the rotated lanes, the
    same in every row, which no mean averages away."""
    r = 2 * angles.shape[-1]
    turned = apply_rope_half(x[..., :r].astype(jnp.float32), angles)
    turned = (turned * amplitude).astype(x.dtype)
    if r == x.shape[-1]:
        return turned
    return jnp.concatenate([turned, x[..., r:]], axis=-1)


class Block(nn.Module):
    """Attention, then the MLP of the layer's kind (routed, or the dense
    SwiGLU), each on the normed stream and added to it."""
    config: LagunaConfig
    layer: int
    mesh: Any = None

    @nn.compact
    def __call__(self, x, angles, amplitude: float):
        cfg, i = self.config, self.layer
        x = x + Attention(cfg, cfg.heads(i), cfg.sliding(i), self.mesh,
                          name="attn")(
            _norm(cfg)(name="attn_norm")(x), angles, amplitude)
        mlp = (MoE(cfg, self.mesh, name="mlp") if cfg.routed(i)
               else _swiglu(cfg, cfg.dense_width, "mlp"))
        return x + mlp(_norm(cfg)(name="mlp_norm")(x))


class Laguna(nn.Module):
    """``__call__(tokens) -> logits`` (or the final hidden states)."""

    config: LagunaConfig
    mesh: Any = None

    def _constrain(self, x):
        if self.mesh is None:
            return x
        from ray_tpu.parallel.sharding import constrain
        return constrain(x, self.mesh, "batch", "seq", None)

    def position_tables(self, t: int) -> dict:
        """{sliding: (angles [t, r/2], amplitude)}: the sliding layers'
        table over every lane and the full layers' YaRN table over the
        rotated lanes, each made once a model."""
        cfg = self.config
        return {
            True: (rope_freqs(cfg.head_dim, t, cfg.sliding_theta), 1.0),
            False: yarn_freqs(
                cfg.rotated_lanes, t, cfg.full_theta,
                factor=cfg.yarn_factor,
                original_len=cfg.yarn_original_len,
                beta_fast=cfg.yarn_beta_fast, beta_slow=cfg.yarn_beta_slow,
                attention_factor=cfg.yarn_attention_factor)}

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        t = tokens.shape[1]
        if t > cfg.seq_len:
            raise ValueError(f"a row of {t} tokens, {cfg.seq_len} positions")
        tables = self.position_tables(t)
        tracing.note_trace(
            attn_kind="window_global", attn_layers=cfg.layer_kinds,
            attn_heads=",".join(str(cfg.heads(i))
                                for i in range(cfg.n_layer)),
            attn_window=cfg.window, attn_gate="headwise_sigmoid",
            rope_kind="yarn_half|default",
            rope_attention_factor=tables[False][1],
            dense_layers=cfg.n_layer - len(cfg.routed_layers),
            blocks_remat=cfg.remat,
            blocks_remat_keeps=remat.keeps_note(cfg.remat, _BLOCK_KEEPS))
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        with jax.named_scope("embed"):
            x = self._constrain(wte(tokens))
        # a recomputed block keeps ``_BLOCK_KEEPS`` and its core's output
        # and row statistics: no matmul of it and no flash forward
        # kernel runs twice (the module docstring). Static: the
        # amplitude is a number of the config, not of the trace
        block = remat.block(Block, cfg.remat, _BLOCK_KEEPS,
                            static_argnums=(3,))
        with jax.named_scope("blocks"):
            for i in range(cfg.n_layer):
                angles, amplitude = tables[cfg.sliding(i)]
                x = self._constrain(block(cfg, i, self.mesh, name=f"h_{i}")(
                    x, angles, amplitude))
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


def laguna_loss_fn(model: Laguna, ce_chunk: int = 2048):
    """(params, batch) -> ``(loss, report)``; batch = {tokens, targets}.

    The loss is the LM loss alone (no auxiliary loss: ``config.json``
    carries no coefficient), chunked against the untied head. The
    report, which ``train/step.py`` puts beside the loss: ``lm_loss``;
    ``moe_load``, the routes each expert of each routed layer received,
    ``[L, E]``; ``moe_held_route_share``, of all the routes of all
    routed layers the share that landed on the experts held,
    ``moe_absent_route_share``, the rest, and
    ``moe_load_max_over_mean``, the largest expert's routes over the
    mean in the worst layer; ``attn_window_out_rms``, the root mean
    square of the sliding layers' cores' output, before the gate, over
    the rows that see a whole window (``smallthinker_loss_fn``'s)."""
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
        if cfg.routed_layers:
            load = jnp.stack([sown["moe"][f"h_{i}"]["mlp"]["load"][0]
                              for i in cfg.routed_layers])
            share = held_route_share(load, cfg.experts_span)
            report.update(
                moe_load=load, moe_held_route_share=share,
                moe_absent_route_share=1.0 - share,
                moe_load_max_over_mean=jnp.max(
                    load.max(axis=-1) / load.mean(axis=-1)))
        out_sq = jnp.stack([sown["stats"][f"h_{i}"]["attn"]["out_sq"][0]
                            for i in range(cfg.n_layer) if cfg.sliding(i)]
                           or [jnp.float32(0)])    # no sliding layer
        report["attn_window_out_rms"] = jnp.sqrt(out_sq.mean())
        return loss, report

    return loss_fn

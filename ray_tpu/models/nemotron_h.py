"""Nemotron-H: a decoder whose layers are of several kinds, one mixer a
block, in flax, designed for mesh sharding.

The stack walks ``config.pattern``, a string with one letter a layer:

- ``M`` a Mamba-2 mixer (``ops/ssm.py``): ``in_proj`` to the gate
  ``z``, the convolved ``x | B | C`` and the step sizes ``dt``; a
  depthwise causal convolution and SiLU; the selective scan in its
  chunked form; the grouped gated RMSNorm; ``out_proj``;
- ``E`` a mixture of experts (``ops/moe.py::routed_ffn``): the float32
  sigmoid router over every expert with its selection bias, the top
  ``top_k`` renormalised and scaled, un-gated relu^2 experts, of which
  this model may hold a share (``experts_held``), plus a shared expert
  of the same form on every token;
- ``*`` causal self-attention with grouped key/value heads and no
  positional embedding (``positions: "none"``).

Every block is ``x = x + mixer(RMSNorm(x))``; a final RMSNorm and an
untied head follow. The public model it expresses is
**NVIDIA-Nemotron-3-Nano-30B-A3B** (``nemotron_3_nano_30b_a3b``: 52
blocks, 23 ``M``, 23 ``E``, 6 ``*``), the benchmark's third language
model (``nemotron-3-nano-30b-a3b.b1-t8192`` runs nine of its layers
with one chip's share of the experts and of the vocabulary). The stack
is written so that the other decoders could be patterns of it (a GPT-2
block is ``*`` then a dense MLP); it does not move them (ROADMAP C1).

Program scopes (docs/observability.md): ``embed``; ``blocks`` with, by
the layer's kind, ``mamba`` (``in_proj``, ``conv``, ``scan``,
``gate_norm``, ``out_proj`` beneath), ``attn``, or ``mlp`` (``router``,
``dispatch``, ``experts``, ``combine``, ``shared`` beneath); ``loss``.

**``Mamba2Mixer`` has two callers.** It reads of its config only what
``Mamba2Dims`` lists, so any config that carries those fields runs it:
this file's (64 heads of 64 in **8 groups**, chunk **128**, hidden
2,688, never under ``nn.remat``) and ``models/granite.py``'s
(granite-4.0-h-micro: 64 heads of 64 in **1 group**, so that the scan's
kernels make a group's score square in each of its 8 head blocks and
sum ``dB``, ``dC`` over them; chunk **256**; hidden 2,048; every block
under ``nn.remat`` with the scan's two results and the three parts of
``in_proj``'s product kept by name, ``ops/remat.py::SSD_SCAN_OUT``,
``SSD_SCAN_STATES`` and ``IN_PROJ_PARTS``). The parameter tree, the scopes
and the arithmetic are one; the names are the identity here.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.llama import RMSNorm
from ray_tpu.ops import conv1d, gated_norm, ssm
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.moe import held_route_share, routed_ffn
from ray_tpu.ops.pallas import program
from ray_tpu.ops.remat import IN_PROJ_PARTS
from ray_tpu.util import tracing

NANO_30B_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


class Mamba2Dims:
    """What ``Mamba2Mixer`` (with ``_Conv``, ``_GateNorm`` and the
    initialisers) reads of a config, and the two widths that follow
    from it. A config that mixes this in and carries the fields runs
    the mixer: ``NemotronHConfig`` here, ``GraniteHybridConfig`` in
    ``models/granite.py``."""
    n_embd: int
    rms_eps: float
    mamba_heads: int
    mamba_head_dim: int
    ssm_state: int
    ssm_groups: int
    conv_kernel: int
    chunk: int
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    dtype: Any
    param_dtype: Any

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state


@dataclass(frozen=True)
class NemotronHConfig(Mamba2Dims):
    """The keys of a ``nemotron_h`` ``config.json`` under this repo's
    names; the defaults are Nemotron-3-Nano-30B-A3B's."""
    vocab_size: int = 131072
    pattern: str = NANO_30B_PATTERN     # hybrid_override_pattern
    n_embd: int = 2688
    seq_len: int = 8192
    rms_eps: float = 1e-5
    # M: Mamba-2
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8                 # n_groups
    conv_kernel: int = 4
    chunk: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # *: attention
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    positions: str = "none"             # the family has no positional embedding
    # E: routed experts and the shared one
    num_experts: int = 128              # the router's width
    top_k: int = 6
    expert_width: int = 1856
    shared_width: int = 3712
    norm_topk_prob: bool = True
    route_scale: float = 2.5
    # (first, count) of the experts this model holds, as one chip of an
    # expert-parallel deployment does; None: all of them
    experts_held: tuple[int, int] | None = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    sp_axis: str = "sp"

    @staticmethod
    def nemotron_3_nano_30b_a3b(**kw) -> "NemotronHConfig":
        """nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``:
        3.2B active of 31.6B parameters."""
        return NemotronHConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "NemotronHConfig":
        """The same shape at test size: every kind of layer, 16 experts
        of which 4 are held, top-3."""
        base = dict(
            vocab_size=256, pattern="MEM*E", n_embd=64, seq_len=64,
            mamba_heads=8, mamba_head_dim=8, ssm_state=16, ssm_groups=2,
            chunk=16, n_head=4, n_kv_head=2, head_dim=16, num_experts=16,
            top_k=3, expert_width=32, shared_width=48,
            experts_held=(4, 4))
        return NemotronHConfig(**{**base, **kw})

    def __post_init__(self):
        if set(self.pattern) - set("ME*") or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: one of M, E, * "
                             "a layer")
        if self.positions != "none":
            raise NotImplementedError(
                f"positions={self.positions!r}: the attention layers of "
                "this stack have no positional embedding")

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    @property
    def experts_span(self) -> tuple[int, int]:
        """(first, count) of the experts held; all of them by default."""
        return self.experts_held or (0, self.num_experts)

    @property
    def held(self) -> int:
        return self.experts_span[1]

    def layer_params(self) -> dict:
        """Parameters of one layer of each kind (with its norm)."""
        d = self.n_embd
        inner, conv = self.mamba_inner, self.conv_width
        return {
            "M": d * (2 * inner + 2 * self.ssm_groups * self.ssm_state
                      + self.mamba_heads)
                 + (self.conv_kernel + 1) * conv + 3 * self.mamba_heads
                 + inner + inner * d + d,
            "E": self.held * 2 * d * self.expert_width
                 + 2 * d * self.shared_width
                 + d * self.num_experts + self.num_experts + d,
            "*": d * (self.n_head + 2 * self.n_kv_head) * self.head_dim
                 + self.n_head * self.head_dim * d + d,
        }

    def num_params(self) -> int:
        per = self.layer_params()
        return (sum(per[kind] for kind in self.pattern)
                + 2 * self.vocab_size * self.n_embd + self.n_embd)


def _dense(cfg: Mamba2Dims):
    return partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype,
                   kernel_init=nn.initializers.normal(0.02))


def _dt_bias_init(cfg: Mamba2Dims):
    """``dt`` log-uniform in [time_step_min, time_step_max], floored;
    the bias is its inverse softplus (the Mamba-2 initialiser)."""
    def init(key, shape, dtype):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo)
                     + lo)
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _a_log_init(key, shape, dtype):
    """``A = -exp(A_log)`` with ``exp(A_log)`` uniform in [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def _conv_init(cfg: Mamba2Dims):
    """torch's ``Conv1d`` default for a depthwise kernel of width K,
    weight and bias: uniform in +-1/sqrt(K)."""
    bound = 1.0 / math.sqrt(cfg.conv_kernel)

    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


class _Conv(nn.Module):
    """``conv1d``: the depthwise causal convolution's [K, C] kernel and
    bias, then SiLU."""
    config: Mamba2Dims
    mesh: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        w = self.param("kernel", _conv_init(cfg),
                       (cfg.conv_kernel, cfg.conv_width), cfg.param_dtype)
        b = self.param("bias", _conv_init(cfg), (cfg.conv_width,),
                       cfg.param_dtype)
        return conv1d.causal_conv1d_silu(x, w, b, mesh=self.mesh)


class _GateNorm(nn.Module):
    config: Mamba2Dims
    mesh: Any = None

    @nn.compact
    def __call__(self, y, z):
        cfg = self.config
        scale = self.param("scale", nn.initializers.ones,
                           (cfg.mamba_inner,), cfg.param_dtype)
        return gated_norm.gated_group_rms_norm(
            y, z, scale, cfg.ssm_groups, cfg.rms_eps, mesh=self.mesh)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer of any config with ``Mamba2Dims``'s fields
    (the module docstring names the two). Where the caller's ``apply``
    makes ``stats`` mutable it sows ``out_sq``, the mean square of the
    scan's output ``y`` before the gate."""
    config: Mamba2Dims
    mesh: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, t, _ = x.shape
        h, p, g, n = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
        inner = cfg.mamba_inner
        zxbcdt = _dense(cfg)(inner + cfg.conv_width + h, name="in_proj")(x)
        # named for a recomputed block's policy; the identity outside one
        z, xbc, dt = map(checkpoint_name, jnp.split(
            zxbcdt, [inner, inner + cfg.conv_width], -1), IN_PROJ_PARTS)
        xbc = _Conv(cfg, self.mesh, name="conv")(xbc)
        xs, bs, cs = jnp.split(xbc, [inner, inner + g * n], -1)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (h,),
                             jnp.float32)
        a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        with jax.named_scope("scan"):
            y = ssm.mamba2_scan(
                xs.reshape(b, t, h, p),
                jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log), bs.reshape(b, t, g, n),
                cs.reshape(b, t, g, n), skip, chunk=cfg.chunk,
                mesh=self.mesh)
        path = ssm.scan_path((b, t, h, p), (b, t, g, n), cfg.chunk,
                             self.mesh)
        tracing.note_trace(
            ssm_tokens=b * t, ssm_heads=h, ssm_state=n, ssm_groups=g,
            ssm_chunk=cfg.chunk, ssm_path=path,
            ssm_blocks_per_group=ssm.score_squares_per_group(h, g, path),
            gate_norm_path=gated_norm.norm_path((b, t, inner), g, self.mesh))
        if self.is_mutable_collection("stats"):
            self.sow("stats", "out_sq",
                     jnp.mean(jnp.square(y.astype(jnp.float32))))
        y = _GateNorm(cfg, self.mesh, name="gate_norm")(
            y.reshape(b, t, inner), z)
        return _dense(cfg)(cfg.n_embd, name="out_proj")(y)


class Attention(nn.Module):
    """Grouped-query causal attention, no bias, no positions."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x, attn_fn: Callable):
        cfg = self.config
        b, t, _ = x.shape
        dense = _dense(cfg)
        q = dense(cfg.n_head * cfg.head_dim, name="q")(x)
        k = dense(cfg.n_kv_head * cfg.head_dim, name="k")(x)
        v = dense(cfg.n_kv_head * cfg.head_dim, name="v")(x)
        q = q.reshape(b, t, cfg.n_head, cfg.head_dim)
        # The kernels take equal head counts: the key/value heads are
        # repeated up to the query heads (16 copies at 32 over 2; what
        # that costs is PERF.md's to say).
        rep = cfg.n_head // cfg.n_kv_head
        k, v = (jnp.repeat(z.reshape(b, t, cfg.n_kv_head, cfg.head_dim),
                           rep, axis=2) for z in (k, v))
        y = attn_fn(q, k, v).reshape(b, t, cfg.n_head * cfg.head_dim)
        return dense(cfg.n_embd, name="proj")(y)


class _Relu2MLP(nn.Module):
    """``down(relu(up x)^2)``: the shared expert."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        up = _dense(cfg)(cfg.shared_width, name="up")(x)
        return _dense(cfg)(cfg.n_embd, name="down")(
            jnp.square(nn.relu(up)))


class _Experts(nn.Module):
    """The stacked weights of the experts held: ``up_proj`` [held, d,
    f] and ``down_proj`` [held, f, d]."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self):
        cfg = self.config
        e, d, f = cfg.held, cfg.n_embd, cfg.expert_width
        init = nn.initializers.normal(0.02)
        return (self.param("up_proj", init, (e, d, f), cfg.param_dtype),
                self.param("down_proj", init, (e, f, d), cfg.param_dtype))


class _Router(nn.Module):
    """``gate``: the router's [d, E] kernel over all experts and the
    score-correction bias [E] (zeros; it takes no gradient)."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self):
        cfg = self.config
        return (self.param("kernel", nn.initializers.normal(0.02),
                           (cfg.n_embd, cfg.num_experts), cfg.param_dtype),
                self.param("e_score_correction_bias",
                           nn.initializers.zeros, (cfg.num_experts,),
                           jnp.float32))


class MoE(nn.Module):
    """The held experts' part of the routed sum, plus the shared
    expert. Sows the routes each expert received."""
    config: NemotronHConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        router_w, select_bias = _Router(cfg, name="gate")()
        up, down = _Experts(cfg, name="experts")()
        y, _, _, load = routed_ffn(
            x, router_w, None, up, down, top_k=cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob, mesh=self.mesh,
            router="sigmoid", select_bias=select_bias,
            route_scale=cfg.route_scale, expert="relu2",
            experts_held=cfg.experts_held)
        self.sow("moe", "load", load)
        return y + _Relu2MLP(cfg, name="shared")(x)


class Block(nn.Module):
    """``x + mixer(RMSNorm(x))``; the mixer is named by its kind, which
    is where the program scope comes from."""
    config: NemotronHConfig
    kind: str
    mesh: Any = None

    @nn.compact
    def __call__(self, x, attn_fn: Callable):
        cfg = self.config
        h = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name="norm")(x)
        if self.kind == "M":
            return x + Mamba2Mixer(cfg, self.mesh, name="mamba")(h)
        if self.kind == "*":
            return x + Attention(cfg, name="attn")(h, attn_fn)
        return x + MoE(cfg, self.mesh, name="mlp")(h)


class NemotronH(nn.Module):
    """``__call__(tokens) -> logits`` (or the final hidden states)."""

    config: NemotronHConfig
    mesh: Any = None

    def _attn_fn(self) -> Callable:
        if self.mesh is None:
            return causal_attention
        from ray_tpu.ops.attention import make_sharded_causal_attention
        return make_sharded_causal_attention(
            self.mesh, seq_axis=self.config.sp_axis)

    def _constrain(self, x):
        if self.mesh is None:
            return x
        from ray_tpu.parallel.sharding import constrain
        return constrain(x, self.mesh, "batch", "seq", None)

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        if "M" in cfg.pattern:
            program.refuse(self.mesh, "a Mamba-2 layer", **{
                cfg.sp_axis: "the state passed from chip to chip that a "
                "sequence split over chips needs (the scan runs a whole "
                "sequence on one chip)"})
        tracing.note_trace(layer_pattern=cfg.pattern)
        with jax.named_scope("embed"):
            x = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         embedding_init=nn.initializers.normal(0.02))(tokens)
            x = self._constrain(x)
        attn_fn = self._attn_fn()
        with jax.named_scope("blocks"):
            for i, kind in enumerate(cfg.pattern):
                x = Block(cfg, kind, self.mesh, name=f"h_{i}")(x, attn_fn)
                x = self._constrain(x)
            x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="norm_f")(x)
        if return_hidden:
            return x        # lm_head's parameters come from init's call
        with jax.named_scope("loss"):
            logits = nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head",
                              dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype)(x)
        return logits.astype(jnp.float32)

    def init_params(self, rng, batch_size: int = 2):
        tokens = jnp.zeros((batch_size, self.config.seq_len), jnp.int32)
        return self.init(rng, tokens)["params"]


def nemotron_h_loss_fn(model: NemotronH, ce_chunk: int = 2048):
    """(params, batch) -> ``(loss, report)``; batch = {tokens, targets}.

    The loss is the LM loss alone (the routed layer of this family has
    no auxiliary loss), chunked against the untied head. The report,
    which ``train/step.py`` puts beside the loss: ``lm_loss``;
    ``moe_held_route_share``, of all the routes of all ``E`` layers the
    share that landed on the experts held (``held / num_experts`` at an
    even load), and ``moe_absent_route_share``, the rest (what an
    expert-parallel deployment sends to other chips);
    ``moe_load_max_over_mean``, the largest expert's routes over the
    mean in the worst layer."""
    from ray_tpu.models.gpt2 import chunked_cross_entropy
    cfg = model.config

    def loss_fn(params, batch):
        hidden, sown = model.apply({"params": params}, batch["tokens"],
                                   return_hidden=True, mutable=["moe"])
        loss = chunked_cross_entropy(
            hidden, params["lm_head"]["kernel"].T, batch["targets"],
            chunk_size=ce_chunk, mesh=model.mesh)
        report = {"lm_loss": loss}
        if "E" in cfg.pattern:
            load = jnp.stack(jax.tree_util.tree_leaves(sown["moe"]))
            share = held_route_share(load, cfg.experts_span)
            report.update(
                moe_held_route_share=share,
                moe_absent_route_share=1.0 - share,
                moe_load_max_over_mean=jnp.max(
                    load.max(axis=-1) / load.mean(axis=-1)))
        return loss, report

    return loss_fn

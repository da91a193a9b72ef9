"""SmallThinker: the window/global decoder with a router in front of
attention, in flax, designed for mesh sharding.

The public model it expresses is **SmallThinker-21BA3B-Instruct**
(PowerInfer, ``model_name: smallthinker_21b_instruct``, "21B-A3B": 52
layers at a hidden size of 2,560; arXiv:2507.20984). Three things set
it apart from ``models/llama.py``'s OLMoE, which is why it is a file of
its own and not a fourth set of fields there (that stack's head width
is ``n_embd // n_head``, its layers are all alike, its router lives
inside its MLP and it holds every expert):

- **a layout over the layers**: ``sliding_window_layout`` and
  ``rope_layout`` are both ``0, 1, 1, 1`` repeated. Layer ``4i`` is
  *global without positions* (NoPE: nothing is rotated, the mask is
  causal); layers ``4i + 1 .. 4i + 3`` are *windowed with RoPE*: q and k
  rotated in halves at theta 1.5e6, and row ``t`` sees keys ``t - 4096
  < j <= t`` (``ops/attention.py::causal_attention(window=...)``, whose
  flash kernels skip the blocks below the band). 28 query heads over 4
  key/value heads of 128, no QK-norm, no bias;
- **a router that reads the block's input**: the routes of a layer's
  experts are made from ``h = RMSNorm_in(x)``, the tensor attention
  reads, before attention runs (``ops/moe.py::route_softmax``: the
  top-6 of 64 softmax probabilities, renormalised over the six), and
  handed to the experts after it; the experts' *input* is the
  post-attention norm as in any decoder;
- **ReGLU experts**: ``down(relu(gate x) * up x)``, 2560 -> 768 ->
  2560, 64 of them, no shared expert, through
  ``ops/moe.py::routed_experts`` (the dropless sort, the held share and
  the grouped matmuls of the other routed models) with ``expert=
  "reglu"``.

A final RMSNorm and an **untied** head; ``smallthinker_loss_fn`` runs
it through the chunked cross-entropy. It is the benchmark's sixth
language model (``smallthinker-21b-a3b.b1-t16384`` runs one period of
four layers with one chip's share of the experts, 16 of 64, and of the
two tables). ``RMSNorm``, ``rope_freqs`` and ``apply_rope_half`` are
``models/llama.py``'s.

Program scopes (docs/observability.md): ``embed``; ``blocks`` with
``h_i/mlp/router`` (the routes, made first), ``h_i/attn`` (``qkv``,
``rope`` in windowed layers only, ``repeat``, the kernel's call under
``window`` in a windowed layer and under ``core`` in a global one,
``out``) and ``h_i/mlp`` (``routed_experts``' own ``router`` for the
count of routes, ``dispatch``, ``experts``, ``combine``); ``loss``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.llama import RMSNorm, apply_rope_half, rope_freqs
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.moe import held_route_share, route_softmax, routed_experts
from ray_tpu.util import tracing


@dataclass(frozen=True)
class SmallThinkerConfig:
    """The keys of a ``smallthinker`` ``config.json`` under this repo's
    names; the defaults are SmallThinker-21BA3B-Instruct's."""
    vocab_size: int = 151936
    n_layer: int = 52                   # num_hidden_layers
    n_embd: int = 2560
    seq_len: int = 16384                # max_position_embeddings
    rms_eps: float = 1e-6
    n_head: int = 28
    n_kv_head: int = 4
    head_dim: int = 128
    # one period of sliding_window_layout / rope_layout: 1 = the layer
    # is windowed / rotates q and k. Layer i reads entry i % len.
    window_period: tuple[int, ...] = (0, 1, 1, 1)
    rope_period: tuple[int, ...] = (0, 1, 1, 1)
    window: int = 4096                  # sliding_window_size
    rope_theta: float = 1_500_000.0
    num_experts: int = 64               # moe_num_primary_experts
    top_k: int = 6                      # moe_num_active_primary_experts
    expert_width: int = 768             # moe_ffn_hidden_size
    norm_topk_prob: bool = True
    # (first, count) of the experts this model holds, as one chip of an
    # expert-parallel deployment does; None: all of them
    experts_held: tuple[int, int] | None = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def smallthinker_21b_a3b(**kw) -> "SmallThinkerConfig":
        """PowerInfer/SmallThinker-21BA3B-Instruct ``config.json``:
        3.0B active of 21.5B."""
        return SmallThinkerConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "SmallThinkerConfig":
        """The same shape at test size: one period of four layers, 4
        query heads over 2 key/value heads of 16, a window of 24 of 64
        rows, 8 ReGLU experts, top-3, of which 4 are held."""
        base = dict(
            vocab_size=256, n_layer=4, n_embd=64, seq_len=64, n_head=4,
            n_kv_head=2, head_dim=16, window=24, rope_theta=10000.0,
            num_experts=8, top_k=3, expert_width=48, experts_held=(4, 4))
        return SmallThinkerConfig(**{**base, **kw})

    def __post_init__(self):
        if self.n_head % self.n_kv_head:
            raise ValueError(f"{self.n_head} query heads over "
                             f"{self.n_kv_head} key/value heads")

    def windowed(self, layer: int) -> bool:
        return bool(self.window_period[layer % len(self.window_period)])

    def rotated(self, layer: int) -> bool:
        return bool(self.rope_period[layer % len(self.rope_period)])

    @property
    def layer_kinds(self) -> str:
        """A letter a layer: ``G`` global, ``W`` windowed; lower case
        where the layer does not rotate q and k (``gWWW`` a period)."""
        kinds = ("W" if self.windowed(i) else "G"
                 for i in range(self.n_layer))
        return "".join(kind if self.rotated(i) else kind.lower()
                       for i, kind in enumerate(kinds))

    @property
    def experts_span(self) -> tuple[int, int]:
        """(first, count) of the experts held; all of them by default."""
        return self.experts_held or (0, self.num_experts)

    @property
    def held(self) -> int:
        return self.experts_span[1]

    def layer_params(self) -> dict:
        """Parameters of a layer by part: ``attn`` (four projections),
        ``router``, the ``experts`` held, ``rest`` (two norms)."""
        d, hd = self.n_embd, self.head_dim
        return {"attn": 2 * d * self.n_head * hd + 2 * d * self.n_kv_head * hd,
                "router": d * self.num_experts,
                "experts": self.held * 3 * d * self.expert_width,
                "rest": 2 * d}

    def num_params(self) -> int:
        return (self.n_layer * sum(self.layer_params().values())
                + 2 * self.vocab_size * self.n_embd + self.n_embd)


def _dense(cfg: SmallThinkerConfig):
    return partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype,
                   kernel_init=nn.initializers.normal(0.02))


def _norm(cfg: SmallThinkerConfig):
    return partial(RMSNorm, eps=cfg.rms_eps, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype)


class Attention(nn.Module):
    """GQA over the layer's mask: ``attn_fn`` is the windowed or the
    global function (``SmallThinker._attn_fns``), ``angles`` None in a
    layer without positions."""
    config: SmallThinkerConfig
    windowed: bool

    @nn.compact
    def __call__(self, h, attn_fn: Callable, angles):
        cfg = self.config
        b, t, _ = h.shape
        hd = cfg.head_dim
        with jax.named_scope("qkv"):
            q = _dense(cfg)(cfg.n_head * hd, name="q")(h)
            k = _dense(cfg)(cfg.n_kv_head * hd, name="k")(h)
            v = _dense(cfg)(cfg.n_kv_head * hd, name="v")(h)
        q = q.reshape(b, t, cfg.n_head, hd)
        k = k.reshape(b, t, cfg.n_kv_head, hd)
        v = v.reshape(b, t, cfg.n_kv_head, hd)
        if angles is not None:
            with jax.named_scope("rope"):
                q = apply_rope_half(q, angles[:t])
                k = apply_rope_half(k, angles[:t])
        rep = cfg.n_head // cfg.n_kv_head
        if rep > 1:
            # The equal-width kernels want as many key/value heads as
            # query heads: each is written ``rep`` times to HBM (and
            # its cotangent summed over the copies). GQA-native K/V in
            # the kernel is ROADMAP B2's; this scope shows what the
            # copies cost.
            with jax.named_scope("repeat"):
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
        with jax.named_scope("window" if self.windowed else "core"):
            o = attn_fn(q, k, v)
        if self.windowed:
            # What a whole window hands the output projection: the mean
            # square of the rows that see ``window`` keys (all alike, so
            # the mean is over 16,384 x 3,584 like entries and a
            # rounding of the operands moves it only by its bias).
            whole = o[:, min(cfg.window, t) - 1:].astype(jnp.float32)
            self.sow("stats", "out_sq", jnp.mean(jnp.square(whole)))
        with jax.named_scope("out"):
            return _dense(cfg)(cfg.n_embd, name="out")(
                o.reshape(b, t, cfg.n_head * hd))


class Router(nn.Module):
    """The bias-free [d, E] router on the block's normed input: (weights
    [B, T, k] float32, experts [B, T, k])."""
    config: SmallThinkerConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (cfg.n_embd, cfg.num_experts), cfg.param_dtype)
        return route_softmax(h, kernel, top_k=cfg.top_k,
                             norm_topk_prob=cfg.norm_topk_prob,
                             mesh=self.mesh)


class Experts(nn.Module):
    """The held ReGLU experts on routes made elsewhere: ``gate_proj``
    and ``up_proj`` [held, d, f], ``down_proj`` [held, f, d]. Sows the
    routes each expert received."""
    config: SmallThinkerConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h, weights, experts):
        cfg = self.config
        e, d, f = cfg.held, cfg.n_embd, cfg.expert_width
        init = nn.initializers.normal(0.02)
        y, load = routed_experts(
            h, weights, experts,
            self.param("gate_proj", init, (e, d, f), cfg.param_dtype),
            self.param("up_proj", init, (e, d, f), cfg.param_dtype),
            self.param("down_proj", init, (e, f, d), cfg.param_dtype),
            num_experts=cfg.num_experts, mesh=self.mesh,
            experts_held=cfg.experts_held, expert="reglu")
        self.sow("moe", "load", load)
        return y


class Block(nn.Module):
    """``h = norm(x)``; the routes from ``h``; ``x += attn(h)``; ``x +=
    experts(norm(x), routes)``."""
    config: SmallThinkerConfig
    layer: int
    mesh: Any = None

    @nn.compact
    def __call__(self, x, attn_fns, angles):
        cfg = self.config
        windowed = cfg.windowed(self.layer)
        h = _norm(cfg)(name="attn_norm")(x)
        # The routed layer's first piece, run before attention: under
        # ``mlp`` so that the readers of a routed layer's time find it.
        with jax.named_scope("mlp"):
            weights, experts = Router(cfg, self.mesh, name="router")(h)
        x = x + Attention(cfg, windowed, name="attn")(
            h, attn_fns[windowed], angles if cfg.rotated(self.layer) else None)
        return x + Experts(cfg, self.mesh, name="mlp")(
            _norm(cfg)(name="mlp_norm")(x), weights, experts)


class SmallThinker(nn.Module):
    """``__call__(tokens) -> logits`` (or the final hidden states)."""

    config: SmallThinkerConfig
    mesh: Any = None

    def _attn_fns(self) -> tuple[Callable, Callable]:
        """(the global layers' attention, the windowed layers')."""
        cfg = self.config
        if self.mesh is None:
            return (causal_attention,
                    partial(causal_attention, window=cfg.window))
        from ray_tpu.ops.attention import make_sharded_causal_attention
        return (make_sharded_causal_attention(self.mesh),
                make_sharded_causal_attention(self.mesh, window=cfg.window))

    def _constrain(self, x):
        if self.mesh is None:
            return x
        from ray_tpu.parallel.sharding import constrain
        return constrain(x, self.mesh, "batch", "seq", None)

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        tracing.note_trace(
            attn_kind="window_global", attn_layers=cfg.layer_kinds,
            attn_window=cfg.window, moe_router_input="pre_attention")
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        with jax.named_scope("embed"):
            x = self._constrain(wte(tokens))
        angles = rope_freqs(cfg.head_dim, cfg.seq_len, cfg.rope_theta)
        attn_fns = self._attn_fns()
        with jax.named_scope("blocks"):
            for i in range(cfg.n_layer):
                x = self._constrain(Block(
                    cfg, i, self.mesh, name=f"h_{i}")(x, attn_fns, angles))
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


def smallthinker_loss_fn(model: SmallThinker, ce_chunk: int = 2048):
    """(params, batch) -> ``(loss, report)``; batch = {tokens, targets}.

    The loss is the LM loss alone (no auxiliary loss: ``config.json``
    carries no coefficient), chunked against the untied head. The
    report, which ``train/step.py`` puts beside the loss: ``lm_loss``;
    ``moe_load``, the routes each expert of each layer received,
    ``[n_layer, E]``; ``moe_held_route_share``, of all the routes of all
    layers the share that landed on the experts held,
    ``moe_absent_route_share``, the rest, and
    ``moe_load_max_over_mean``, the largest expert's routes over the
    mean in the worst layer; ``attn_window_out_rms``, the root mean
    square of the windowed cores' output over the rows that see a whole
    window (rows ``t >= window - 1``; the last row of a sequence shorter
    than the window) and the windowed layers."""
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
        out_sq = jnp.stack([sown["stats"][f"h_{i}"]["attn"]["out_sq"][0]
                            for i in range(cfg.n_layer) if cfg.windowed(i)]
                           or [jnp.float32(0)])    # no windowed layer
        return loss, {
            "lm_loss": loss, "moe_load": load,
            "attn_window_out_rms": jnp.sqrt(out_sq.mean()),
            "moe_held_route_share": share,
            "moe_absent_route_share": 1.0 - share,
            "moe_load_max_over_mean": jnp.max(
                load.max(axis=-1) / load.mean(axis=-1))}

    return loss_fn

"""Ouro: the looped decoder (one stack of layers run several times with
one set of weights, an exit gate and a head after every pass), in flax,
designed for mesh sharding.

The public model it expresses is **Ouro-2.6B** (ByteDance, the LoopLM of
arXiv:2510.25741: 48 layers at a hidden size of 2,048, 16 heads of 128,
SwiGLU at 5,632, an untied 49,152-row table, ``total_ut_steps`` 4). A
token's row, ``L`` layers, ``R`` passes:

- ``h_0 = E[x]``; pass ``t = 1..R`` runs **the same ``L`` parameter
  sets**: ``u = h_{t-1}``, then a layer: ``u = u + RMSNorm_2(Attn(
  RMSNorm_1(u)))``; ``u = u + RMSNorm_4(MLP(RMSNorm_3(u)))``: four norms
  a layer (one before and one after each sub-layer, the second on the
  branch before it is added), no bias, RoPE on every lane in the
  ``rotate_half`` layout, the same positions in every pass;
- ``h_t = RMSNorm_f(u)``: the one final norm closes **every** pass, and
  its output is what pass ``t + 1`` starts from;
- after every pass the head's logits ``z_t = W_head h_t`` and the exit
  gate ``lambda_t = sigmoid(w_g . h_t + b_g)``;
- ``ouro_loss_fn``: with ``S_t = prod_{j<=t} (1 - lambda_j)`` the exit
  distribution is ``p_t = lambda_t S_{t-1}`` for ``t < R`` and ``p_R =
  S_{R-1}`` (the last pass takes what is left); the loss is the mean over
  tokens of ``sum_t p_t l_t - beta H(p)``, ``l_t`` pass ``t``'s
  cross-entropy: **a loss weighted row by row by what the model itself
  learned**, through ``models/gpt2.py::chunked_cross_entropy_rows`` over
  the ``R`` passes' rows against the one head.

**The tree has ``L`` blocks whatever ``R``** (``h_0..h_{L-1}``,
``norm_f``, ``exit_gate``, ``wte``, ``lm_head``): the passes are one
``nn.scan`` over the stack with the parameters broadcast, so the program
holds ``L`` blocks and every block leaf's gradient is the sum over the
passes that the scan's transpose makes. With ``remat`` a block is
recomputed in the backward pass but for its attention core's output and
row statistics (``ops/remat.py::remat_policy``), as
``models/laguna.py``, and for its MLP's matmul products
(``_mlp_keeps``): what ``R x L`` applications keep is each one's input
and those. The MLP's names are chosen by the milliseconds a kept GB
saves, which the contraction's depth sets: ``down``'s product (``[T,
d]``, made by a matmul ``intermediate`` deep, and read in the backward
pass because the block norms it on the branch) before ``up``'s and
``gate``'s (``[T, intermediate]``, ``d`` deep), for as long as the
cell's memory lasts. On the chip that is ``down`` and ``up`` in every
layer and ``gate`` in the second half of them (``_mlp_keeps``): with
``gate`` kept in all, the step at 8 layers x 4 passes x 4,096 rows no
longer fits beside its own backward loop, XLA recomputes ``down``'s
transposed product to make room, and the step is slower than with two
names (docs/training_perf.md).

``sp`` and ``tp`` meshes are refused by name: the passes' rows are
stacked on the sequence axis for the loss (``[B, R T, d]``: the batch
axis stays what ``dp`` / ``fsdp`` shard), which an ``sp`` shard of ``T``
does not survive, and the 2,048 -> 1 gate and the stacked loss have no
``tp`` path.

It is the benchmark's tenth language model (``ouro-2.6b.b1-t4096`` runs
8 of the 48 layers, four passes). ``rope_freqs``, ``apply_rope_half``
and ``SwiGLU`` are ``models/llama.py``'s (its ``RMSNorm`` through
``models/joyai.py``'s ``_norm``, which with ``_dense`` reads only the
fields this config shares with that one, as ``models/laguna.py``).

Program scopes (docs/observability.md): ``embed``; ``blocks`` with
``h_i/attn`` (``qkv``, ``rope``, ``repeat``, ``core``, ``out``),
``h_i/mlp``, the four norms ``h_i/attn_norm``, ``h_i/attn_post_norm``,
``h_i/mlp_norm``, ``h_i/mlp_post_norm``, ``norm_f`` and ``exit_gate``;
``loss`` (the head and every pass's ``l_t``) with ``loss/exit`` (the
distribution, the entropy, the weighting).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.joyai import _dense, _norm
from ray_tpu.models.llama import SwiGLU, apply_rope_half, rope_freqs
from ray_tpu.ops import remat
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.pallas import program
from ray_tpu.ops.remat import MLP_DOWN, MLP_GATE, MLP_UP
from ray_tpu.util import tracing


def _mlp_keeps(cfg) -> dict[str, int]:
    """What a recomputed block keeps of its MLP, dearest a byte first
    (the module docstring), as ``{name: the first layer that keeps
    it}``: at 4,096 rows 16.8 MB (down) and 46.1 MB each (up, gate) an
    application."""
    return {MLP_DOWN: 0, MLP_UP: 0,
            # the second half of the stack alone: where memory ends the
            # list, it ends it layer by layer (on the chip the last four
            # of eight read 466.3 ms a step, the first four 468.1, three
            # 469.8, five 473.9, none 478.9, all 493.5: PERF.md section
            # 6, PR 62)
            MLP_GATE: cfg.n_layer // 2}


@dataclass(frozen=True)
class OuroConfig:
    """The keys of an ``ouro`` ``config.json`` under this repo's names;
    the defaults are Ouro-2.6B's."""
    vocab_size: int = 49152
    n_layer: int = 48                   # num_hidden_layers
    ut_steps: int = 4                   # total_ut_steps: passes a token
    n_head: int = 16
    n_kv_head: int = 16
    head_dim: int = 128
    n_embd: int = 2048
    intermediate: int = 5632
    rope_theta: float = 1_000_000.0
    rms_eps: float = 1e-6
    exit_beta: float = 0.05             # the entropy's weight in the loss
    seq_len: int = 65536                # max_position_embeddings
    remat: bool = False                 # recompute each block in backward
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def ouro_2_6b(**kw) -> "OuroConfig":
        """ByteDance/Ouro-2.6B ``config.json``."""
        return OuroConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "OuroConfig":
        """The same shape at test size: 2 layers run 4 times, 4 heads of
        16 over 2 key/value heads, 64 rows."""
        base = dict(vocab_size=256, n_layer=2, n_head=4, n_kv_head=2,
                    head_dim=16, n_embd=64, intermediate=176, seq_len=64,
                    rope_theta=10000.0)
        return OuroConfig(**{**base, **kw})

    def __post_init__(self):
        if self.n_head % self.n_kv_head:
            raise ValueError(f"{self.n_head} query heads over "
                             f"{self.n_kv_head} key/value heads")
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps {self.ut_steps}")

    def layer_params(self) -> dict:
        """Parameters of a layer by part: ``attn`` (W_q, W_k, W_v, W_o),
        ``mlp`` (gate, up, down), ``norms`` (four scales)."""
        d, hd = self.n_embd, self.head_dim
        return {"attn": 2 * d * self.n_head * hd + 2 * d * self.n_kv_head * hd,
                "mlp": 3 * d * self.intermediate, "norms": 4 * d}

    def num_params(self) -> int:
        """``n_layer`` blocks whatever ``ut_steps``, the two tables, the
        final norm, the gate and its bias."""
        d = self.n_embd
        return (self.n_layer * sum(self.layer_params().values())
                + 2 * self.vocab_size * d + d + d + 1)


def _attn_fn(mesh):
    if mesh is None:
        return causal_attention
    from ray_tpu.ops.attention import make_sharded_causal_attention
    return make_sharded_causal_attention(mesh)


class Attention(nn.Module):
    """Causal attention over every key up to the row, RoPE on every lane
    in halves."""
    config: OuroConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, h, angles):
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
        with jax.named_scope("rope"):
            q = apply_rope_half(q, angles[:t])
            k = apply_rope_half(k, angles[:t])
        rep = cfg.n_head // cfg.n_kv_head
        if rep > 1:     # not the published model's: 16 over 16
            with jax.named_scope("repeat"):
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
        with jax.named_scope("core"):
            o = _attn_fn(self.mesh)(q, k, v)
        with jax.named_scope("out"):
            return _dense(cfg)(cfg.n_embd, name="out")(
                o.reshape(b, t, cfg.n_head * hd))


class Block(nn.Module):
    """The four-norm ("sandwich") block: each sub-layer reads the normed
    stream, and its output is normed on the branch before it is added."""
    config: OuroConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, angles):
        cfg = self.config
        x = x + _norm(cfg)(name="attn_post_norm")(
            Attention(cfg, self.mesh, name="attn")(
                _norm(cfg)(name="attn_norm")(x), angles))
        return x + _norm(cfg)(name="mlp_post_norm")(
            SwiGLU(cfg, name="mlp")(_norm(cfg)(name="mlp_norm")(x)))


class ExitGate(nn.Module):
    """``w_g . h + b_g``, 2,048 -> 1, float32: a product and a sum over
    the lanes, not a matmul (a float32 dot at the TPU's default precision
    would round both operands to bfloat16)."""
    config: OuroConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        w = self.param("kernel", nn.initializers.normal(0.02),
                       (cfg.n_embd, 1), cfg.param_dtype)
        b = self.param("bias", nn.initializers.zeros, (1,), cfg.param_dtype)
        return (jnp.sum(h.astype(jnp.float32) * w[:, 0].astype(jnp.float32),
                        axis=-1) + b[0].astype(jnp.float32))


def _one_pass(mdl, h, angles):
    """The stack, the final norm and the exit gate once, on ``mdl``'s
    own parameters: ``h_{t-1} -> (h_t, (h_t, the gate's logit))``."""
    cfg = mdl.config
    for i in range(cfg.n_layer):
        block = remat.block(Block, cfg.remat, _mlp_keeps(cfg), i)
        h = mdl._constrain(block(cfg, mdl.mesh, name=f"h_{i}")(h, angles))
    h = _norm(cfg)(name="norm_f")(h)
    return h, (h, ExitGate(cfg, name="exit_gate")(h))


class Ouro(nn.Module):
    """``__call__(tokens)`` -> (every pass's logits ``[B, R, T, V]``, the
    gate's logit after every pass ``[B, R, T]``); with ``return_hidden``
    the passes' final hidden states ``[B, R, T, d]`` in the logits'
    place."""

    config: OuroConfig
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
            self.mesh, "Ouro",
            sp="the sequence split over chips (the passes' rows are "
               "stacked on the sequence axis for the loss)",
            tp="a tp path for the exit gate")
        t = tokens.shape[1]
        if t > cfg.seq_len:
            raise ValueError(f"a row of {t} tokens, {cfg.seq_len} positions")
        tracing.note_trace(
            attn_kind="looped_full", ut_steps=cfg.ut_steps, ut_path="scan",
            rope_kind="half", blocks_remat=cfg.remat,
            blocks_remat_keeps=remat.keeps_note(cfg.remat, _mlp_keeps(cfg)))
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        with jax.named_scope("embed"):
            x = self._constrain(wte(tokens))
        angles = rope_freqs(cfg.head_dim, t, cfg.rope_theta)
        with jax.named_scope("blocks"):
            # one program of n_layer blocks under a loop of ut_steps;
            # the passes' outputs are stacked behind the batch axis
            _, (hs, gate) = nn.scan(
                _one_pass, variable_broadcast="params",
                split_rngs={"params": False}, in_axes=nn.broadcast,
                out_axes=1, length=cfg.ut_steps)(self, x, angles)
        if return_hidden:
            # For the chunked loss, which never makes a row's logits;
            # the head's parameters exist regardless: initialisation
            # traces the plain path.
            return hs, gate
        with jax.named_scope("loss"):
            return _dense(cfg)(cfg.vocab_size, name="lm_head")(hs).astype(
                jnp.float32), gate

    def init_params(self, rng, batch_size: int = 2):
        """Traced on a short row: no parameter's shape reads the
        sequence, and the untied head's logits over every pass of a whole
        row are not made at initialisation."""
        t = min(self.config.seq_len, 128)
        return self.init(rng, jnp.zeros((batch_size, t), jnp.int32))["params"]


def exit_distribution(gate):
    """(``log p`` ``[B, R, T]``, float32) from the gate's logits after
    each pass: ``p_t = lambda_t S_{t-1}``, ``t < R``, and ``p_R =
    S_{R-1}``, ``S_t = prod_{j<=t} (1 - lambda_j)``, in logarithms
    (``log lambda = log_sigmoid(g)``, ``log (1 - lambda) =
    log_sigmoid(-g)``) so that a gate far from 0 loses no digit."""
    g = gate.astype(jnp.float32)
    log_stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=1)      # log S_t
    before = jnp.pad(log_stay[:, :-1], ((0, 0), (1, 0), (0, 0)))
    log_p = jax.nn.log_sigmoid(g) + before
    return log_p.at[:, -1].set(before[:, -1])


def ouro_loss_fn(model: Ouro, ce_chunk: int = 2048):
    """(params, batch) -> ``(loss, report)``; batch = {tokens, targets}.

    The loss is the paper's stage I objective: the mean over unmasked
    tokens of ``sum_t p_t l_t - exit_beta H(p)``. The ``R`` passes' rows
    go through one call of ``chunked_cross_entropy_rows`` against the
    one head (``[B, R T, d]``: the head's gradient is accumulated in one
    backward scan, not ``R``), which hands back each row's ``l_t`` and
    takes ``p_t`` over the count as its cotangent. The report, which
    ``train/step.py`` puts beside the loss: ``lm_loss_ut_1`` ..
    ``lm_loss_ut_R`` (the mean ``l_t`` a pass), ``exit_mean_step`` (the
    mean of ``sum_t t p_t``) and ``exit_entropy`` (the mean ``H(p)``)."""
    from ray_tpu.models.gpt2 import chunked_cross_entropy_rows
    cfg = model.config
    r = cfg.ut_steps

    def loss_fn(params, batch):
        hs, gate = model.apply({"params": params}, batch["tokens"],
                               return_hidden=True)
        b, _, t, d = hs.shape
        targets = batch["targets"]
        rows = chunked_cross_entropy_rows(
            hs.reshape(b, r * t, d), params["lm_head"]["kernel"].T,
            jnp.tile(targets, (1, r)), chunk_size=ce_chunk, mesh=model.mesh)
        with jax.named_scope("loss"), jax.named_scope("exit"):
            nll = rows.reshape(b, r, t)                 # l_t, masked rows 0
            mask = (targets != -1).astype(jnp.float32)[:, None]
            count = jnp.maximum(mask.sum(), 1.0)
            log_p = exit_distribution(gate)
            p = jnp.exp(log_p)
            entropy = -(p * log_p).sum(1, keepdims=True)
            loss = (((p * nll).sum(1, keepdims=True)
                     - cfg.exit_beta * entropy) * mask).sum() / count
            steps = jnp.arange(1, r + 1, dtype=jnp.float32)[None, :, None]
            per_pass = nll.sum((0, 2)) / count
            report = {
                **{f"lm_loss_ut_{i + 1}": per_pass[i] for i in range(r)},
                "exit_mean_step": ((p * steps).sum(1, keepdims=True)
                                   * mask).sum() / count,
                "exit_entropy": (entropy * mask).sum() / count}
        return loss, report

    return loss_fn

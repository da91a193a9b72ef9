"""GPT-2 in flax, designed for mesh sharding.

The flagship model for the north-star benchmark (BASELINE.json: GPT-2
tokens/sec/chip). TPU-first choices:

- bfloat16 compute / float32 params (MXU-native).
- param names line up with ``parallel.sharding.DEFAULT_PARAM_PATTERNS``
  so dp/fsdp/tp sharding is a table lookup, no per-model plumbing.
- attention is pluggable: dense (Pallas flash kernel on single-device
  TPU, shard_map-wrapped per-device flash on a mesh, XLA
  ``dot_product_attention`` elsewhere) or ring attention over an
  ``sp`` mesh axis for long context (SURVEY.md §5.7 — capability the
  reference lacks natively).
- activations carry logical sharding constraints ("batch", "seq") so
  pjit propagates the intended layout instead of guessing.
- optional remat (``jax.checkpoint``) per block: trade FLOPs for HBM.
"""

from __future__ import annotations

from dataclasses import dataclass
import functools
import math
from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import causal_attention, ring_attention
from ray_tpu.ops.pallas import program
from ray_tpu.util import tracing


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304          # 50257 padded up for MXU tiling
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    seq_len: int = 1024
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16        # compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "auto"          # "auto" | "dense" | "ring"
    sp_axis: str = "sp"

    @staticmethod
    def small(**kw) -> "GPT2Config":
        return GPT2Config(**kw)

    @staticmethod
    def medium(**kw) -> "GPT2Config":
        return GPT2Config(n_layer=24, n_head=16, n_embd=1024, **kw)

    @staticmethod
    def large(**kw) -> "GPT2Config":
        return GPT2Config(n_layer=36, n_head=20, n_embd=1280, **kw)

    @staticmethod
    def tiny(**kw) -> "GPT2Config":
        """Test-size config for CPU-mesh runs."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("n_layer", 2)
        kw.setdefault("n_head", 4)
        kw.setdefault("n_embd", 64)
        kw.setdefault("seq_len", 64)
        return GPT2Config(**kw)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    def num_params(self) -> int:
        e, l, v, s = self.n_embd, self.n_layer, self.vocab_size, \
            self.seq_len
        per_block = 12 * e * e + 13 * e  # qkv+proj+mlp + norms/biases
        return v * e + s * e + l * per_block + 2 * e


class CausalSelfAttention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, attn_fn: Callable, deterministic: bool = True):
        cfg = self.config
        B, T, _ = x.shape
        # The qkv projection: one kernel kept as [E, 3, H, D] (the
        # sharding table's qkv pattern splits its heads over tp),
        # applied as three matmuls that see the heads merged. q, k and
        # v then each come out [B, T, H*D] as a matmul wrote them —
        # the layout the flash kernel's blocks index — and the
        # kernel's output is the output projection's operand as it
        # stands: nothing is copied on either side. What this avoids
        # (PR 25's trace: 36.6 ms of a 250 ms step): an array whose
        # last dimension is D=64 is half padding in the chip's
        # 128-lane tiles, so XLA lays a [.., H, 64] matmul output out
        # with T innermost and copies it on the way to the kernel; and
        # one matmul whose [B, 3, T, H*D] output is sliced three ways
        # pays for the slices (226.2 against 220.5 ms a step on the
        # v5e, PERF.md section 6, PR 29).
        kernel_init = nn.initializers.normal(0.02)
        qkv_w = self.param(
            "qkv_kernel", kernel_init,
            (cfg.n_embd, 3, cfg.n_head, cfg.head_dim),
            cfg.param_dtype)
        qkv_b = self.param(
            "qkv_bias", nn.initializers.zeros,
            (3, cfg.n_head, cfg.head_dim), cfg.param_dtype)
        x = x.astype(cfg.dtype)
        w = qkv_w.astype(cfg.dtype).reshape(cfg.n_embd, 3, -1)
        bias = qkv_b.astype(cfg.dtype).reshape(3, -1)
        q, k, v = (
            (jnp.einsum("bte,ef->btf", x, w[:, i])
             + bias[i]).reshape(B, T, cfg.n_head, cfg.head_dim)
            for i in range(3))
        y = attn_fn(q, k, v)
        proj_w = self.param(
            "proj_kernel",
            nn.initializers.normal(0.02 / (2 * cfg.n_layer) ** 0.5),
            (cfg.n_head, cfg.head_dim, cfg.n_embd), cfg.param_dtype)
        proj_b = self.param("proj_bias", nn.initializers.zeros,
                            (cfg.n_embd,), cfg.param_dtype)
        y = jnp.einsum("btf,fe->bte",
                       y.astype(cfg.dtype).reshape(B, T, -1),
                       proj_w.astype(cfg.dtype).reshape(-1, cfg.n_embd)) \
            + proj_b.astype(cfg.dtype)
        if cfg.dropout > 0:
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        dense = partial(nn.Dense, use_bias=True, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02))
        h = dense(4 * cfg.n_embd, name="fc")(x)
        h = nn.gelu(h)
        h = dense(cfg.n_embd, name="proj",
                  kernel_init=nn.initializers.normal(
                      0.02 / (2 * cfg.n_layer) ** 0.5))(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, attn_fn: Callable, deterministic: bool = True):
        cfg = self.config
        ln = partial(nn.LayerNorm, epsilon=1e-5, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype)
        x = x + CausalSelfAttention(cfg, name="attn")(
            ln(name="ln_1")(x), attn_fn, deterministic)
        x = x + MLP(cfg, name="mlp")(
            ln(name="ln_2")(x), deterministic)
        return x


class GPT2(nn.Module):
    """GPT-2 LM. ``__call__(tokens) -> logits``; weights tied wte/lm."""

    config: GPT2Config
    mesh: Any = None  # jax.sharding.Mesh | None — enables sp attention

    def _attn_fn(self) -> Callable:
        cfg = self.config
        if self.mesh is None:
            return causal_attention
        # Which path (ring, the kernel under shard_map, the kernel
        # bare) is the dispatch layer's call, made from the mesh.
        from ray_tpu.ops.attention import make_sharded_causal_attention
        return make_sharded_causal_attention(
            self.mesh, seq_axis=cfg.sp_axis, impl=cfg.attn_impl)

    def _constrain(self, x):
        if self.mesh is None:
            return x
        from ray_tpu.parallel.sharding import constrain
        return constrain(x, self.mesh, "batch", "seq", None)

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True,
                 return_hidden: bool = False):
        cfg = self.config
        B, T = tokens.shape
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        wpe = nn.Embed(cfg.seq_len, cfg.n_embd, name="wpe",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.01))
        # Program scopes (docs/observability.md): every operation of
        # the step falls under ``embed``, ``blocks``, ``loss`` or
        # ``optimizer``; flax puts the module names beneath them.
        with jax.named_scope("embed"):
            pos = jnp.arange(T)[None, :]
            x = wte(tokens) + wpe(pos)
            x = self._constrain(x)
            if cfg.dropout > 0:
                x = nn.Dropout(cfg.dropout)(
                    x, deterministic=deterministic)

        attn_fn = self._attn_fn()
        block_cls = Block
        if cfg.remat:
            block_cls = nn.remat(
                Block, static_argnums=(2, 3),
                policy=jax.checkpoint_policies.nothing_saveable)
        with jax.named_scope("blocks"):
            for i in range(cfg.n_layer):
                x = block_cls(cfg, name=f"h_{i}")(
                    x, attn_fn, deterministic)
                x = self._constrain(x)
            x = nn.LayerNorm(epsilon=1e-5, name="ln_f", dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype)(x)
        if return_hidden:
            # Final hidden states for fused/chunked LM-head losses
            # that never materialize the full (B, S, vocab) logits.
            return x
        # Tied LM head: bf16 operands into the MXU, f32 accumulation
        # and f32 logits out. Operands are rounded to bf16 (small
        # precision trade, ~2^-8 relative) — accepted for full MXU
        # rate; only the accumulation is fp32.
        with jax.named_scope("loss"):
            logits = jnp.einsum(
                "bte,ve->btv", x.astype(self.config.dtype),
                wte.embedding.astype(self.config.dtype),
                preferred_element_type=jnp.float32)
        return logits

    def init_params(self, rng, batch_size: int = 2):
        tokens = jnp.zeros((batch_size, self.config.seq_len),
                           dtype=jnp.int32)
        return self.init(rng, tokens)["params"]


@jax.named_scope("loss")
def cross_entropy_loss(logits, targets, ignore_index: int = -1):
    """Mean token cross-entropy; positions == ignore_index are masked."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = targets != ignore_index
    safe = jnp.where(mask, targets, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(mask, nll, 0.0)
    return nll.sum() / jnp.maximum(mask.sum(), 1)


# The ``loss`` scope is opened inside each half of the custom_vjp:
# a scope around the call alone does not reach the backward ``while``.
# It returns each row's loss (a masked row's is 0), their sum and the
# count of unmasked rows, not a mean: a caller that holds only part of
# the rows adds its sums to the others' first, and one that weights the
# rows (``models/ouro.py``) hands the backward a cotangent a row.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
@jax.named_scope("loss")
def _chunked_ce_core(rows_c, emb, tgt_c, ignore_index, path):
    return _chunked_ce_rows(rows_c, emb, tgt_c, ignore_index, path)[:2]


def _chunk_logits(x_c, emb):
    return jnp.einsum("ce,ve->cv", x_c, emb,
                      preferred_element_type=jnp.float32)


def _chunked_ce_rows_scan(rows_c, emb, tgt_c, ignore_index):
    def one(carry, xt):
        x_c, t_c = xt
        logits = _chunk_logits(x_c, emb)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        mask = t_c != ignore_index
        safe = jnp.where(mask, t_c, 0)
        picked = jnp.take_along_axis(logits, safe[:, None], 1)[:, 0]
        nll = jnp.where(mask, lse - picked, 0.0)
        tot, cnt = carry
        return (tot + nll.sum(), cnt + mask.sum()), (nll, lse)

    sums, (nll, lse) = jax.lax.scan(
        one, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (rows_c, tgt_c))
    return nll, sums, lse


def _chunked_ce_fwd_scan(rows_c, emb, tgt_c, ignore_index):
    _, sums, lse = _chunked_ce_rows_scan(rows_c, emb, tgt_c, ignore_index)
    return sums, lse


def _chunked_ce_rows(rows_c, emb, tgt_c, ignore_index, path):
    """(each row's loss [n, chunk], a masked row's 0; (their sum, the
    count of unmasked rows); lse [n, chunk]) by ``path`` (``ce_path``):
    the scan above, or the kernel of ``ops/pallas/ce_lse.py`` over all
    the rows at once, a row block its own chunk, which writes no logit
    to HBM. The backward pass takes either's ``lse``."""
    if path == "xla_scan":
        return _chunked_ce_rows_scan(rows_c, emb, tgt_c, ignore_index)
    from ray_tpu.ops.pallas import ce_lse
    n, chunk, e = rows_c.shape
    tgt = tgt_c.reshape(-1)
    mask = tgt != ignore_index
    lse, picked = ce_lse.ce_lse_fwd(
        rows_c.reshape(-1, e), emb, jnp.where(mask, tgt, 0))
    nll = jnp.where(mask, lse - picked, 0.0)
    return (nll.reshape(n, chunk), (nll.sum(), mask.sum()),
            lse.reshape(n, chunk))


def _chunked_ce_fwd(rows_c, emb, tgt_c, ignore_index, path):
    """((sum of the rows' losses, count of unmasked rows), lse [n,
    chunk]) of ``_chunked_ce_rows``."""
    return _chunked_ce_rows(rows_c, emb, tgt_c, ignore_index, path)[1:]


@jax.named_scope("loss")
def _chunked_ce_core_fwd(rows_c, emb, tgt_c, ignore_index, path):
    nll, sums, lse_c = _chunked_ce_rows(rows_c, emb, tgt_c, ignore_index,
                                        path)
    return (nll, sums), (rows_c, emb, tgt_c, lse_c)


@jax.named_scope("loss")
def _chunked_ce_core_bwd(ignore_index, path, res, g):
    # Hand-written backward: recompute each chunk's logits but REUSE
    # the saved log-sum-exp (a jax.checkpoint formulation re-runs the
    # full logsumexp reduction too). dlogits = (softmax - onehot) times
    # the row's cotangent: d tot (1/cnt of the caller's mean) on every
    # row, plus the row's own where the caller weighted the rows. The
    # same scan whichever ``path`` made the log-sum-exp.
    rows_c, emb, tgt_c, lse_c = res
    d_rows, (d_tot, _) = g
    scale_c = d_rows + d_tot

    def one(demb, xt):
        x_c, t_c, lse, scale = xt
        logits = _chunk_logits(x_c, emb)
        mask = (t_c != ignore_index)
        p = jnp.exp(logits - lse[:, None])
        safe = jnp.where(mask, t_c, 0)
        onehot = jax.nn.one_hot(safe, logits.shape[-1],
                                dtype=p.dtype)
        dlogits = (p - onehot) * (scale * mask)[:, None]
        dlb = dlogits.astype(emb.dtype)
        dx = jax.lax.dot_general(
            dlb, emb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x_c.dtype)
        demb = demb + jax.lax.dot_general(
            dlb, x_c, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return demb, dx

    demb0 = jnp.zeros(emb.shape, jnp.float32)
    demb, dx_c = jax.lax.scan(one, demb0,
                              (rows_c, tgt_c, lse_c, scale_c))
    return dx_c, demb.astype(emb.dtype), None


_chunked_ce_core.defvjp(_chunked_ce_core_fwd, _chunked_ce_core_bwd)


def _loss_axes(mesh, batch: int, seq: int):
    """(batch axes, sequence axis or None) that the loss's ``shard_map``
    splits the rows of a ``[batch, seq, ...]`` array over
    (``program.token_axes``: dp and fsdp on the batch, sp on the
    sequence); ``((), None)`` where the chunk scan has to stay one
    global scan."""
    from ray_tpu.parallel.sharding import DEFAULT_RULES

    if (mesh is not None
            and DEFAULT_RULES.mesh_axis("vocab", mesh) is not None):
        # The head is sharded on its vocabulary axis (tp): mapping
        # over the token axes alone would hand every chip the whole
        # head. A vocabulary-parallel cross-entropy is another path.
        return (), None
    return program.token_axes(mesh, batch, seq)


def _mapped_over(axes) -> tuple:
    """The mesh axes ``_loss_axes`` found, as one tuple: what the
    loss's ``shard_map`` splits the rows over (none: one program)."""
    batch_axes, seq_axis = axes
    return batch_axes + ((seq_axis,) if seq_axis else ())


def _chunks(rows: int, chunk_size: int):
    """(rows of a chunk, chunks) that ``rows`` rows are padded to."""
    chunk = min(chunk_size, rows)
    return chunk, -(-rows // chunk)


def ce_path(shape, vocab: int, dtype, mesh=None,
            chunk_size: int = 2048) -> str:
    """What ``chunked_cross_entropy`` compiles for the forward pass of
    ``hidden`` [B, S, E] against a head of ``vocab`` rows on this mesh:
    ``pallas_lse``, the kernel of ``ops/pallas/ce_lse.py``, on a TPU
    backend where the rows are bfloat16, ``E`` and ``vocab`` are whole
    128-lane tiles, the padded rows whole sublane tiles, and the
    program that holds the rows is one device's: no mesh on a
    one-device process, a one-device mesh, or a chip's rows under the
    ``shard_map`` over the token axes (``_loss_axes``); ``xla_scan``,
    the scan over chunks, everywhere else (the CPU, float32 rows, a
    head sharded on its vocabulary or shapes the axes do not divide,
    where one program spans the devices and a ``pallas_call`` has no
    partitioning rule). Every head shape of the benchmark's cells is
    the kernel's: it beat the scan at each on the chip (PERF.md
    section 6, PR 51)."""
    from ray_tpu.ops.pallas import ce_lse
    batch, seq, e = shape
    over = _mapped_over(_loss_axes(mesh, batch, seq))
    if not over and (jax.device_count() if mesh is None
                     else mesh.size) > 1:
        return "xla_scan"
    devices = math.prod(mesh.shape[a] for a in over)
    chunk, n = _chunks(batch * seq // devices, chunk_size)
    if (jax.default_backend() == "tpu" and dtype == jnp.bfloat16
            and ce_lse.shapes_ok(n * chunk, e, vocab)):
        return "pallas_lse"
    return "xla_scan"


@jax.named_scope("loss")
def chunked_cross_entropy(hidden, embedding, targets,
                          ignore_index: int = -1,
                          chunk_size: int = 2048, mesh=None):
    """Cross-entropy that never materializes the full (B, S, vocab)
    logits: the tied LM head + loss run per row-chunk with a
    hand-written VJP (bwd recomputes each chunk's logits but reuses
    the saved per-row log-sum-exp).

    TPU rationale: full GPT-2 logits are B*S*50304 f32 — 6.6 GB at
    the bench shape — and the softmax/backward over them is pure HBM
    traffic. The backward keeps the live logits block at
    chunk_size*vocab (~400 MB at 2048), trading one extra LM-head
    matmul for most of that bandwidth. The forward, where ``ce_path``
    says ``pallas_lse``, writes no logit to HBM at all: one kernel
    over all the rows folds a ``[512, 1024]`` block of them at a time
    into each row's running maximum and sum in VMEM
    (``ops/pallas/ce_lse.py``); under ``xla_scan`` it is a scan over
    the same chunks as the backward, a chunk's float32 logits written
    by the matmul and read back by the log-sum-exp.

    On a ``mesh`` that shards tokens (dp, fsdp, sp) each chip chunks
    and scans the rows it holds, under ``shard_map``; the two sums and
    the head's gradient are reduced over those axes once, after the
    scan. ``mesh=None``, a one-device mesh, shapes the axes do not
    divide and a head sharded on its vocabulary (tp) keep one global
    scan: the first two have nothing to split, the others would
    gather what the caller sharded (a scan walks its leading axis in
    order, so a sharded chunk axis is gathered to every chip).
    """
    tot, cnt = _chunked_ce(hidden, embedding, targets, ignore_index,
                           chunk_size, mesh, by_row=False)
    return tot / jnp.maximum(cnt, 1).astype(jnp.float32)


@jax.named_scope("loss")
def chunked_cross_entropy_rows(hidden, embedding, targets,
                               ignore_index: int = -1,
                               chunk_size: int = 2048, mesh=None):
    """``chunked_cross_entropy`` before its mean: each row's loss,
    float32 ``[B, S]``, a masked row's 0, differentiable a row (the
    backward takes a cotangent a row). For a loss that weights the rows
    by something the model learned (``models/ouro.py``: the exit
    distribution over the passes, the passes' hidden states stacked on
    the batch axis against the one head, so the head's gradient is
    accumulated in one backward scan). The same core, forward paths
    and ``shard_map`` over the token axes as the mean's; on a mesh the
    rows come back sharded as ``targets`` are."""
    return _chunked_ce(hidden, embedding, targets, ignore_index,
                       chunk_size, mesh, by_row=True)


def _chunked_ce(hidden, embedding, targets, ignore_index, chunk_size, mesh,
                by_row: bool):
    """``by_row``: each row's loss ``[B, S]``; else (their sum, the
    count of unmasked rows), over the whole mesh."""
    B, S, E = hidden.shape
    # Cast the tied embedding ONCE outside the scan (fwd and bwd both
    # consume the bf16 copy).
    emb = embedding.astype(hidden.dtype)
    path = ce_path(hidden.shape, emb.shape[0], hidden.dtype, mesh,
                   chunk_size)

    def in_hand(hidden, emb, targets, over=()):
        """Of the rows in hand: their losses as ``targets`` is shaped,
        or (the losses' sum, the count of unmasked rows) added over the
        mesh axes ``over``."""
        rows = hidden.reshape(-1, E)
        tgt = targets.reshape(-1)
        chunk, n = _chunks(rows.shape[0], chunk_size)
        pad = n * chunk - rows.shape[0]
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
            tgt = jnp.pad(tgt, (0, pad), constant_values=ignore_index)
        nll, tot_cnt = _chunked_ce_core(
            rows.reshape(n, chunk, E), emb, tgt.reshape(n, chunk),
            ignore_index, path)
        tracing.count_trace(ce_rows=n * chunk)
        if path == "pallas_lse":
            from ray_tpu.ops.pallas import ce_lse
            block_rows, tile = ce_lse.blocks(n * chunk, E, emb.shape[0])
            tracing.note_trace(ce_fwd_rows=block_rows, ce_fwd_tile=tile)
            tracing.count_trace(ce_fwd_calls=1)
        if by_row:
            return nll.reshape(-1)[:targets.size].reshape(targets.shape)
        return jax.lax.psum(tot_cnt, over) if over else tot_cnt

    tracing.note_trace(ce_path=path)
    batch_axes, seq_axis = axes = _loss_axes(mesh, B, S)
    over = _mapped_over(axes)
    if not over:
        return in_hand(hidden, emb, targets)
    from jax.sharding import PartitionSpec
    tokens = PartitionSpec(batch_axes or None, seq_axis)
    tracing.note_trace(
        ce_rows_local=B * S // math.prod(mesh.shape[a] for a in over),
        ce_axes=list(over))
    # The head enters replicated, so the transpose reduces its
    # gradient over the axes: once, after the backward scan. All
    # mesh axes are manual, as in ops/attention.py: with only the
    # token axes manual (``axis_names``) the bf16 sum's reduction
    # gets a sharding constraint that aborts XLA's CPU compiler.
    return jax.shard_map(
        functools.partial(in_hand, over=() if by_row else over), mesh=mesh,
        in_specs=(tokens, PartitionSpec(), tokens),
        out_specs=tokens if by_row else PartitionSpec(),
        check_vma=False)(hidden, emb, targets)


def gpt2_loss_fn(model: GPT2, fused_ce: bool = True,
                 ce_chunk: int = 2048):
    """(params, batch) -> scalar loss; batch = {tokens, targets}.

    ``fused_ce`` (default) uses the chunked LM-head + cross-entropy
    path; False materializes full logits (kept for A/B and for
    callers that need them)."""

    def loss_fn(params, batch):
        if fused_ce:
            h = model.apply({"params": params}, batch["tokens"],
                            return_hidden=True)
            return chunked_cross_entropy(
                h, params["wte"]["embedding"], batch["targets"],
                chunk_size=ce_chunk, mesh=model.mesh)
        logits = model.apply({"params": params}, batch["tokens"])
        return cross_entropy_loss(logits, batch["targets"])

    return loss_fn

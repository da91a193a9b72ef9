"""Operations, bytes and parameters of a Kimi-Linear-shaped stack (Kimi
Delta Attention and latent-attention mixers by a published list, a
leading dense layer, routed SwiGLU experts with a shared one, an untied
head), from shapes alone. As in ``flops.py``: required operations only,
a multiply-add is two, recomputation does not count (the convolutions,
norms, decays and recurrences that the backward pass runs again are not
in here). ``c`` is anything with the fields of
``ray_tpu.models.kimi_linear.KimiLinearConfig`` (only its numbers are
read).
"""

from __future__ import annotations

from types import SimpleNamespace

from benchlib import flops_mla


def _held(c) -> int:
    return c.experts_held[1] if c.experts_held else c.num_experts


def layers_of(c) -> tuple[int, int]:
    """(KDA layers, MLA layers) of the stack; ``mla_layers`` count from
    1, as the published list does."""
    mla = sum(i + 1 in c.mla_layers for i in range(c.n_layer))
    return c.n_layer - mla, mla


def _as_joyai(c, **kw):
    """``c`` as ``flops_mla``'s functions read a config: no MTP module."""
    fields = ("n_layer", "dense_layers", "seq_len", "n_head", "nope_dim",
              "rope_dim", "v_dim", "n_embd", "expert_width", "top_k",
              "num_experts", "experts_held")
    return SimpleNamespace(**{**{k: getattr(c, k) for k in fields},
                              "mtp_depth": 0, **kw})


def layer_params(c) -> dict:
    """Parameters by part, as ``KimiLinearConfig.layer_params``."""
    d, inner, r, h = c.n_embd, c.kda_heads * c.kda_head_dim, c.kda_rank, \
        c.n_head
    return {
        "kda": (4 * d * inner + 3 * c.conv_kernel * inner
                + 2 * (d * r + r * inner) + 2 * inner + c.kda_heads
                + d * c.kda_heads + c.kda_head_dim),
        "mla": (d * h * (c.nope_dim + c.rope_dim)
                + d * (c.kv_rank + c.rope_dim) + c.kv_rank
                + c.kv_rank * h * (c.nope_dim + c.v_dim) + h * c.v_dim * d),
        "dense": 3 * d * c.dense_width,
        "routed": (d * c.num_experts + c.num_experts + 3 * d * c.shared_width
                   + _held(c) * 3 * d * c.expert_width),
        "norms": 2 * d}


def num_params(c) -> int:
    per = layer_params(c)
    kda, mla = layers_of(c)
    return (kda * per["kda"] + mla * per["mla"] + c.n_layer * per["norms"]
            + c.dense_layers * per["dense"]
            + (c.n_layer - c.dense_layers) * per["routed"]
            + 2 * c.vocab_size * c.n_embd + c.n_embd)


def kda_recurrence_macs_per_token(c) -> float:
    """Multiply-adds a token of one layer's recurrences at the stated
    chunk ``C``, every head: the key-key and query-key products under
    the diagonal (``C^2 K / 2`` each), the unit-triangular solve against
    ``V + K`` columns (``C^2 (V + K) / 2``), three ``[C, K] x [K, V]``
    products with the state (the correction's read, the output's read,
    the write) and the outputs' ``C^2 V / 2`` inside the chunk; a chunk
    is ``C`` tokens."""
    ch, k = c.kda_chunk, c.kda_head_dim
    per_chunk = ch * ch * k + ch * ch * k + 3 * ch * k * k + ch * ch * k / 2
    return c.kda_heads * per_chunk / ch


def forward_flops_per_token(c) -> dict:
    """Forward operations a token by part, one block each (``head`` once
    a step): 2 per matmul weight the token meets (the convolutions'
    taps, the norms and the gates are not matmuls); the recurrence at
    the stated chunk; the MLA core's QK^T (192 wide) and PV (128 wide)
    over half the square; the routed experts at an even load."""
    d, inner = c.n_embd, c.kda_heads * c.kda_head_dim
    routes = c.top_k * _held(c) / c.num_experts
    return {
        "kda_proj": 2.0 * (4 * d * inner + 2 * (d * c.kda_rank
                                                + c.kda_rank * inner)
                           + d * c.kda_heads),
        "kda_scan": 2.0 * kda_recurrence_macs_per_token(c),
        "mla_proj": 2.0 * (d * c.n_head * (c.nope_dim + c.rope_dim)
                           + d * (c.kv_rank + c.rope_dim)
                           + c.kv_rank * c.n_head * (c.nope_dim + c.v_dim)
                           + c.n_head * c.v_dim * d),
        "attn_core": (2.0 * c.seq_len * c.n_head
                      * (c.nope_dim + c.rope_dim + c.v_dim) * 0.5),
        "dense_mlp": 2.0 * 3 * d * c.dense_width,
        "shared": 2.0 * 3 * d * c.shared_width,
        "held_experts": routes * 2.0 * 3 * d * c.expert_width,
        "router": 2.0 * d * c.num_experts,
        "head": 2.0 * d * c.vocab_size,
    }


def step_forward_flops_per_token(c) -> dict:
    """The same by part, summed over the step's blocks."""
    per = forward_flops_per_token(c)
    kda, mla = layers_of(c)
    routed = c.n_layer - c.dense_layers
    times = {"kda_proj": kda, "kda_scan": kda, "mla_proj": mla,
             "attn_core": mla, "dense_mlp": c.dense_layers,
             "shared": routed, "held_experts": routed, "router": routed,
             "head": 1}
    return {k: v * times[k] for k, v in per.items()}


def train_flops_per_token(c) -> float:
    """Forward + backward: three times the forward (each matmul's
    backward is one pass for its input and one for its weights)."""
    return 3.0 * sum(step_forward_flops_per_token(c).values())


def kda_scan_train_cost(c, batch: int) -> dict:
    """Operations and HBM bytes that the KDA layers' recurrences need
    for one training step, whatever implements them: the chunked form's
    matmuls at the stated chunk, forward and twice that backward; and
    q, k, v, o, o's cotangent and the cotangents of q, k and v read or
    written once each at two bytes, the log-decay ``g`` and its
    cotangent at four (a decay is float32 in any implementation),
    ``beta`` and its cotangent at four."""
    kda, _ = layers_of(c)
    tokens = batch * c.seq_len
    inner = c.kda_heads * c.kda_head_dim
    flops = kda * tokens * 3 * 2.0 * kda_recurrence_macs_per_token(c)
    per_token = 8 * inner * 2 + 2 * inner * 4 + 2 * c.kda_heads * 4
    return {"flops": flops, "bytes": kda * tokens * per_token}


def mla_core_train_cost(c, batch: int) -> dict:
    """The MLA layers' attention cores, by
    ``flops_mla.latent_attention_train_cost``'s rule."""
    return flops_mla.latent_attention_train_cost(
        _as_joyai(c, n_layer=layers_of(c)[1]), batch)


def held_experts_train_cost(c, tokens: int) -> dict:
    """The held SwiGLU experts' grouped matmuls of every routed block,
    by ``flops_mla.held_experts_train_cost``'s rule."""
    return flops_mla.held_experts_train_cost(_as_joyai(c), tokens)

"""Operations, bytes and parameters of a Qwen3-Next-shaped stack (Gated
DeltaNet mixers with key heads under value heads and a decay a head, one
gated grouped-query attention layer a period, softmax-routed SwiGLU
experts beside a gated shared one in every block, an untied head), from
shapes alone. As in ``flops.py``: required operations only, a
multiply-add is two, recomputation does not count (what a recomputed
block runs again is not in here), **whatever implements them**. ``c`` is
anything with the fields of ``ray_tpu.models.qwen3_next.Qwen3NextConfig``
(only its numbers are read).
"""

from __future__ import annotations

from benchlib import flops


def _held(c) -> int:
    return c.experts_held[1] if c.experts_held else c.num_experts


def layers_of(c) -> tuple[int, int]:
    """(Gated DeltaNet layers, attention layers) of the stack: layer
    ``i``, counted from 0, is attention when ``(i + 1) %
    full_attention_interval == 0``."""
    full = sum((i + 1) % c.full_attention_interval == 0
               for i in range(c.n_layer))
    return c.n_layer - full, full


def layer_params(c) -> dict:
    """Parameters by part, as ``Qwen3NextConfig.layer_params``."""
    d, hd, kd = c.n_embd, c.head_dim, c.gdn_head_dim
    keys, inner, h = c.gdn_key_heads * kd, c.gdn_value_heads * kd, \
        c.gdn_value_heads
    return {
        "gdn": (d * (2 * keys + 2 * inner) + d * 2 * h
                + c.conv_kernel * (2 * keys + inner) + 2 * h + kd
                + inner * d),
        "attn": (d * 2 * c.n_head * hd + 2 * d * c.n_kv_head * hd
                 + c.n_head * hd * d + 2 * hd),
        "moe": d * c.num_experts + 3 * d * c.shared_width + d,
        "expert": 3 * d * c.expert_width,
        "norms": 2 * d}


def num_params(c) -> int:
    per = layer_params(c)
    gdn, full = layers_of(c)
    return (gdn * per["gdn"] + full * per["attn"] + c.n_layer * (
        per["norms"] + per["moe"] + _held(c) * per["expert"])
        + 2 * c.vocab_size * c.n_embd + c.n_embd)


def gdn_recurrence_macs_per_token(c) -> float:
    """Multiply-adds a token of one layer's recurrences at the stated
    chunk ``C``: the key-key and query-key products under the diagonal
    (``C^2 K / 2`` each) **once a key head** (the value heads of a key
    head share them; their decay matrices are elementwise), and a value
    head the unit-triangular solve against ``V + K`` columns (``C^2 (V
    + K) / 2``), three ``[C, K] x [K, V]`` products with the state and
    the outputs' ``C^2 V / 2`` inside the chunk; a chunk is ``C``
    tokens."""
    ch, k = c.gdn_chunk, c.gdn_head_dim
    a_key_head = ch * ch * k
    a_value_head = ch * ch * k + 3 * ch * k * k + ch * ch * k / 2
    return (c.gdn_key_heads * a_key_head
            + c.gdn_value_heads * a_value_head) / ch


def forward_flops_per_token(c) -> dict:
    """Forward operations a token by part, one block each (``head`` once
    a step): 2 per matmul weight the token meets (the convolution's
    taps, the norms and the elementwise gates are not matmuls); the
    recurrence at the stated chunk; the attention core's QK^T and PV at
    ``head_dim`` over half the square; the routed experts at an even
    load."""
    d, hd, kd = c.n_embd, c.head_dim, c.gdn_head_dim
    keys, inner = c.gdn_key_heads * kd, c.gdn_value_heads * kd
    routes = c.top_k * _held(c) / c.num_experts
    return {
        "gdn_proj": 2.0 * (d * (2 * keys + 2 * inner)
                           + d * 2 * c.gdn_value_heads + inner * d),
        "gdn_scan": 2.0 * gdn_recurrence_macs_per_token(c),
        "attn_proj": 2.0 * (d * 2 * c.n_head * hd + 2 * d * c.n_kv_head * hd
                            + c.n_head * hd * d),
        "attn_core": 2.0 * c.seq_len * c.n_head * 2 * hd * 0.5,
        "shared": 2.0 * (3 * d * c.shared_width + d),
        "held_experts": routes * 2.0 * 3 * d * c.expert_width,
        "router": 2.0 * d * c.num_experts,
        "head": 2.0 * d * c.vocab_size,
    }


def step_forward_flops_per_token(c) -> dict:
    """The same by part, summed over the step's blocks."""
    per = forward_flops_per_token(c)
    gdn, full = layers_of(c)
    times = {"gdn_proj": gdn, "gdn_scan": gdn, "attn_proj": full,
             "attn_core": full, "shared": c.n_layer,
             "held_experts": c.n_layer, "router": c.n_layer, "head": 1}
    return {k: v * times[k] for k, v in per.items()}


def train_flops_per_token(c) -> float:
    """Forward + backward: three times the forward (each matmul's
    backward is one pass for its input and one for its weights)."""
    return 3.0 * sum(step_forward_flops_per_token(c).values())


def gdn_scan_train_cost(c, batch: int) -> dict:
    """Operations and HBM bytes that the Gated DeltaNet layers'
    recurrences need for one training step, whatever implements them:
    the chunked form's matmuls at the stated chunk, forward and twice
    that backward; and ``q``, ``k`` and their cotangents at the key
    heads' width, ``v``, ``o``, ``o``'s cotangent and ``v``'s at the
    value heads', once each at two bytes; ``g``, ``beta`` and their
    cotangents one float32 a row a head."""
    gdn, _ = layers_of(c)
    tokens = batch * c.seq_len
    keys = c.gdn_key_heads * c.gdn_head_dim
    inner = c.gdn_value_heads * c.gdn_head_dim
    per_token = (4 * keys + 4 * inner) * 2 + 4 * c.gdn_value_heads * 4
    return {"flops": gdn * tokens * 3 * 2.0 * gdn_recurrence_macs_per_token(c),
            "bytes": gdn * tokens * per_token}


def flash_core_train_cost(c, batch: int) -> dict:
    """The attention layers' cores at ``n_head`` query heads of
    ``head_dim`` (256), by ``flops.flash_attention_train_cost``'s rule
    (the key/value heads counted as the query heads are: what
    equal-width operands move; GQA-native K and V would move less)."""
    return flops.flash_attention_train_cost(
        batch, c.n_head, c.seq_len, c.head_dim, layers_of(c)[1])


def held_experts_train_cost(c, tokens: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes of the held SwiGLU experts' grouped
    matmuls for one training step, every layer's, over the routes held
    at an even load (``tokens * top_k * held / E`` rows): three matrices
    (gate, up, down), each once forward and twice backward; each of
    those nine grouped matmuls reads its rows, reads or writes each held
    expert's matrix once and writes its result (``flops_moe``'s
    reckoning)."""
    rows = tokens * c.top_k * _held(c) / c.num_experts
    d, f = c.n_embd, c.expert_width
    per_matmul = rows * d + rows * f + _held(c) * d * f
    return {"flops": c.n_layer * 6.0 * rows * 3 * d * f,
            "bytes": c.n_layer * 9 * per_matmul * bytes_per_el}

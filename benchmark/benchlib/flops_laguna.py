"""Operations, bytes and parameters of a Laguna-shaped stack (full and
sliding attention layers whose head counts differ by layer, a gate a
head, a leading dense MLP, routed SwiGLU experts of which a share is
held beside a shared one, an untied head), from shapes alone. As in
``flops.py``: required operations only, a multiply-add is two,
recomputation does not count (a recomputed block's second forward is not
in here). **The band is counted, not the triangle**
(``flops_smallthinker.seen_entries``): a sliding layer's core needs the
score entries a row sees and no others, whatever blocks a kernel walks.
``c`` is anything with the fields of
``ray_tpu.models.laguna.LagunaConfig`` (only its numbers and its three
per-layer lists are read; a layer's kind, head count and MLP are its
entries of those lists).
"""

from __future__ import annotations

from benchlib.flops_smallthinker import seen_entries

SLIDING, SPARSE = "sliding_attention", "sparse"


def _held(c) -> int:
    return c.experts_held[1] if c.experts_held else c.num_experts


def _sliding(c, layer: int) -> bool:
    return c.layer_types[layer] == SLIDING


def _routed(c, layer: int) -> bool:
    return c.mlp_layer_types[layer] == SPARSE


def routed_layers(c) -> int:
    return sum(_routed(c, i) for i in range(c.n_layer))


def attn_weights(c, layer: int) -> int:
    """W_q, W_k, W_v, W_o and the gate W_g at the layer's head count."""
    d, hd, h = c.n_embd, c.head_dim, c.heads_per_layer[layer]
    return 2 * d * h * hd + 2 * d * c.n_kv_head * hd + d * h


def layer_params(c, layer: int) -> dict:
    """Parameters of ``layer`` by part, as ``LagunaConfig.layer_params``:
    ``attn``, the ``dense`` MLP or the ``router`` (its bias counts), the
    ``shared`` expert and the ``experts`` held, ``rest`` (two norms)."""
    d = c.n_embd
    parts = {"attn": attn_weights(c, layer), "rest": 2 * d}
    if not _routed(c, layer):
        return {**parts, "dense": 3 * d * c.dense_width}
    return {**parts, "router": d * c.num_experts + c.num_experts,
            "shared": 3 * d * c.shared_width,
            "experts": _held(c) * 3 * d * c.expert_width}


def num_params(c) -> int:
    return (sum(sum(layer_params(c, i).values()) for i in range(c.n_layer))
            + 2 * c.vocab_size * c.n_embd + c.n_embd)


def step_forward_flops_per_token(c) -> dict:
    """Forward operations a token by part, summed over the step's
    layers: 2 per matmul weight the token meets; an attention core's
    QK^T and PV at the layer's head count over the keys a row sees on
    average (the band in a sliding layer: 504.0 of 16,384 under a window
    of 512; half the square and half a diagonal in a full one); the
    routed experts at an even load (``held / E`` of a token's ``top_k``
    routes land here); the head once."""
    d, hd, t = c.n_embd, c.head_dim, c.seq_len
    out = dict.fromkeys(("attn_proj", "core_window", "core_full",
                         "dense_mlp", "shared", "held_experts", "router",
                         "head"), 0.0)
    for i in range(c.n_layer):
        h = c.heads_per_layer[i]
        out["attn_proj"] += 2.0 * attn_weights(c, i)
        per_key = 2.0 * h * 2 * hd
        if _sliding(c, i):
            out["core_window"] += per_key * seen_entries(t, c.window) / t
        else:
            out["core_full"] += per_key * seen_entries(t, None) / t
        if _routed(c, i):
            out["shared"] += 2.0 * 3 * d * c.shared_width
            out["router"] += 2.0 * d * c.num_experts
            out["held_experts"] += (c.top_k * _held(c) / c.num_experts
                                    * 2.0 * 3 * d * c.expert_width)
        else:
            out["dense_mlp"] += 2.0 * 3 * d * c.dense_width
    out["head"] = 2.0 * d * c.vocab_size
    return out


def train_flops_per_token(c) -> float:
    """Forward + backward: three times the forward (each matmul's
    backward is one pass for its input and one for its weights)."""
    return 3.0 * sum(step_forward_flops_per_token(c).values())


def _cores_train_cost(c, batch: int, sliding: bool,
                      bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes that the attention cores of one kind
    need for one training step as the equal-width kernel sees them (the
    layer's ``H_l`` heads of q, k and v each, the key/value heads
    already repeated): six matmuls (QK^T, PV; dV, dP, dQ, dK) over the
    entries the mask lets through, the backward's second run of the
    scores not counted; forward reads q, k, v and writes o, backward
    reads q, k, v, o, dO and writes dq, dk, dv, each once, and the
    per-row float32 statistics (``flops_smallthinker._cores_train_cost``
    a layer at a time, at each layer's own head count)."""
    t = c.seq_len
    seen = seen_entries(t, c.window if sliding else None)
    flops = bytes_ = 0.0
    for i in range(c.n_layer):
        if _sliding(c, i) != sliding:
            continue
        bh = batch * c.heads_per_layer[i]
        flops += bh * 6 * 2.0 * seen * c.head_dim
        tensor = bh * t * c.head_dim * bytes_per_el
        rows = bh * t * 4
        bytes_ += (4 * tensor + rows) + (8 * tensor + 2 * rows)
    return {"flops": flops, "bytes": bytes_}


def window_cores_train_cost(c, batch: int) -> dict:
    """The sliding layers' cores: the band, exactly."""
    return _cores_train_cost(c, batch, True)


def full_cores_train_cost(c, batch: int) -> dict:
    """The full layers' cores: the triangle."""
    return _cores_train_cost(c, batch, False)


def flash_cores_train_cost(c, batch: int) -> dict:
    """Every layer's core: what the custom calls under ``attn`` have to
    do (``attn_flash_roofline``)."""
    parts = (window_cores_train_cost(c, batch),
             full_cores_train_cost(c, batch))
    return {k: sum(p[k] for p in parts) for k in ("flops", "bytes")}


def held_experts_train_cost(c, tokens: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes of the held SwiGLU experts' grouped
    matmuls for one training step, every routed layer's, over the routes
    held at an even load (``tokens * top_k * held / E`` rows): three
    matrices (gate, up, down), each once forward and twice backward;
    each of those nine grouped matmuls reads its rows, reads or writes
    each held expert's matrix once and writes its result
    (``flops_moe``'s reckoning)."""
    layers = routed_layers(c)
    rows = tokens * c.top_k * _held(c) / c.num_experts
    d, f = c.n_embd, c.expert_width
    per_matmul = rows * d + rows * f + _held(c) * d * f
    return {"flops": layers * 6.0 * rows * 3 * d * f,
            "bytes": layers * 9 * per_matmul * bytes_per_el}

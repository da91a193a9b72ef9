"""Operations, bytes and parameters of a SmallThinker-shaped stack
(windowed and global attention layers by a layout, a router read before
attention, routed ReGLU experts of which a share is held, an untied
head), from shapes alone. As in ``flops.py``: required operations only,
a multiply-add is two, recomputation does not count. **The band is
counted, not the triangle**: a windowed layer's core needs the score
entries a row sees and no others, whatever blocks a kernel walks. ``c``
is anything with the fields of
``ray_tpu.models.smallthinker.SmallThinkerConfig`` (only its numbers are
read).
"""

from __future__ import annotations


def _held(c) -> int:
    return c.experts_held[1] if c.experts_held else c.num_experts


def _windowed(c, layer: int) -> bool:
    return bool(c.window_period[layer % len(c.window_period)])


def layers_of(c) -> tuple[int, int]:
    """(windowed layers, global layers) of the stack."""
    w = sum(_windowed(c, i) for i in range(c.n_layer))
    return w, c.n_layer - w


def layer_params(c) -> dict:
    """Parameters of a layer by part, as
    ``SmallThinkerConfig.layer_params``: ``attn`` (W_q, W_k, W_v, W_o),
    ``router``, the ``experts`` held, ``rest`` (two norms)."""
    d, hd = c.n_embd, c.head_dim
    return {"attn": 2 * d * c.n_head * hd + 2 * d * c.n_kv_head * hd,
            "router": d * c.num_experts,
            "experts": _held(c) * 3 * d * c.expert_width,
            "rest": 2 * d}


def num_params(c) -> int:
    return (c.n_layer * sum(layer_params(c).values())
            + 2 * c.vocab_size * c.n_embd + c.n_embd)


def seen_entries(t: int, window: int | None) -> int:
    """Score entries of one head's [t, t] that the mask lets through:
    row r (from 0) sees ``min(r + 1, window)`` keys. No window: the
    triangle, ``t (t + 1) / 2``."""
    w = t if window is None else min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def forward_flops_per_token(c) -> dict:
    """Forward operations a token by part, one layer each (``head``
    once a step): 2 per matmul weight the token meets; an attention
    core's QK^T and PV at ``n_head`` heads over the keys a row sees on
    average (the band in a windowed layer: 3,584.1 of 16,384 under a
    window of 4,096; half the square and half a diagonal in a global
    one); the routed experts at an even load (``held / E`` of a token's
    ``top_k`` routes land here)."""
    d, hd = c.n_embd, c.head_dim
    per_key = 2.0 * c.n_head * 2 * hd
    return {
        "attn_proj": 2.0 * layer_params(c)["attn"],
        "core_window": per_key * seen_entries(c.seq_len, c.window)
        / c.seq_len,
        "core_global": per_key * seen_entries(c.seq_len, None) / c.seq_len,
        "router": 2.0 * d * c.num_experts,
        "held_experts": c.top_k * _held(c) / c.num_experts * 2.0 * 3 * d
        * c.expert_width,
        "head": 2.0 * d * c.vocab_size,
    }


def step_forward_flops_per_token(c) -> dict:
    """The same by part, summed over the step's layers."""
    per = forward_flops_per_token(c)
    w, g = layers_of(c)
    times = {"core_window": w, "core_global": g, "head": 1}
    return {k: v * times.get(k, c.n_layer) for k, v in per.items()}


def train_flops_per_token(c) -> float:
    """Forward + backward: three times the forward (each matmul's
    backward is one pass for its input and one for its weights)."""
    return 3.0 * sum(step_forward_flops_per_token(c).values())


def _cores_train_cost(c, batch: int, layers: int, window: int | None,
                      bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes that ``layers`` attention cores need for
    one training step as the equal-width kernel sees them (``n_head``
    heads of q, k and v each, the key/value heads already repeated): six
    matmuls (QK^T, PV; dV, dP, dQ, dK) over the entries the mask lets
    through, the backward's second run of the scores not counted;
    forward reads q, k, v and writes o, backward reads q, k, v, o, dO
    and writes dq, dk, dv, each once, and the per-row float32 statistics
    (``flops.flash_attention_train_cost``'s reckoning, which this is
    with no window)."""
    bh = batch * c.n_head
    flops = layers * bh * 6 * 2.0 * seen_entries(c.seq_len, window) \
        * c.head_dim
    tensor = bh * c.seq_len * c.head_dim * bytes_per_el
    rows = bh * c.seq_len * 4
    return {"flops": flops,
            "bytes": layers * ((4 * tensor + rows) + (8 * tensor + 2 * rows))}


def window_cores_train_cost(c, batch: int) -> dict:
    """The windowed layers' cores: the band, exactly."""
    return _cores_train_cost(c, batch, layers_of(c)[0], c.window)


def global_cores_train_cost(c, batch: int) -> dict:
    """The global layers' cores: the triangle."""
    return _cores_train_cost(c, batch, layers_of(c)[1], None)


def flash_cores_train_cost(c, batch: int) -> dict:
    """Every layer's core: what the custom calls under ``attn`` have to
    do (``attn_flash_roofline``)."""
    parts = (window_cores_train_cost(c, batch),
             global_cores_train_cost(c, batch))
    return {k: sum(p[k] for p in parts) for k in ("flops", "bytes")}


def held_experts_train_cost(c, tokens: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes of the held ReGLU experts' grouped
    matmuls for one training step, every layer's, over the routes held
    at an even load (``tokens * top_k * held / E`` rows): three matrices
    (gate, up, down), each once forward and twice backward; each of
    those nine grouped matmuls reads its rows, reads or writes each
    held expert's matrix once and writes its result (``flops_moe``'s
    reckoning)."""
    rows = tokens * c.top_k * _held(c) / c.num_experts
    d, f = c.n_embd, c.expert_width
    per_matmul = rows * d + rows * f + _held(c) * d * f
    return {"flops": c.n_layer * 6.0 * rows * 3 * d * f,
            "bytes": c.n_layer * 9 * per_matmul * bytes_per_el}

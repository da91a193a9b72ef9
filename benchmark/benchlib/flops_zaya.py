"""Operations, bytes and parameters of a ZAYA1-shaped stack (CCA, a
top-1 MLP router over a state that runs from layer to layer, routed
SwiGLU experts of which a share is held, scaled residuals, a tied
table), from shapes alone. As in ``flops.py``: required operations
only, a multiply-add is two, recomputation does not count. ``c`` is
anything with the fields of ``ray_tpu.models.zaya.ZayaConfig`` (only its
numbers are read).
"""

from __future__ import annotations


def _held(c) -> int:
    return c.experts_held[1] if c.experts_held else c.num_experts


def _latent(c) -> int:
    return (c.n_head + c.n_kv_head) * c.head_dim


def layer_params(c) -> dict:
    """Parameters of a layer by part, as ``ZayaConfig.layer_params``:
    ``cca`` (W_q | W_k, W_v1 | W_v2, W_o, the two convolutions with
    their biases, the temperature), ``router`` (W_d and its bias,
    ``gamma``, the three-layer MLP, the balancing bias), the
    ``experts`` held, ``rest`` (two norms, 4 x d scales and biases a
    sublayer)."""
    d, hd, r = c.n_embd, c.head_dim, c.router_width
    heads = c.n_head + c.n_kv_head
    k0, k1 = c.conv_taps
    return {
        "cca": (d * _latent(c) + d * c.n_kv_head * hd + c.n_head * hd * d
                + (k0 + 1) * _latent(c) + k1 * heads * hd * hd + _latent(c)
                + c.n_kv_head),
        "router": (d * r + r + r + 2 * (r * r + r) + r * c.num_experts
                   + 2 * c.num_experts),
        "experts": _held(c) * 3 * d * c.expert_width,
        "rest": 2 * d + 8 * d}


def num_params(c) -> int:
    return (c.n_layer * sum(layer_params(c).values())
            + c.vocab_size * c.n_embd + c.n_embd)


def forward_flops_per_token(c) -> dict:
    """Forward operations a token by part, one layer each (``head``
    once a step): 2 per matmul weight the token meets; the convolution
    within heads is a [D, D] matmul a tap a head; the attention core's
    QK^T and PV at ``n_head`` heads over half the square; the routed
    experts at an even load (``held / E`` of a token's one route lands
    here)."""
    d, hd = c.n_embd, c.head_dim
    heads = c.n_head + c.n_kv_head
    r = c.router_width
    return {
        "cca_proj": 2.0 * (d * _latent(c) + d * c.n_kv_head * hd
                           + c.n_head * hd * d),
        "cca_conv": 2.0 * c.conv_taps[1] * heads * hd * hd,
        "attn_core": 2.0 * c.seq_len * c.n_head * 2 * hd * 0.5,
        "router": 2.0 * (d * r + 2 * r * r + r * c.num_experts),
        "held_experts": _held(c) / c.num_experts * 2.0 * 3 * d
        * c.expert_width,
        "head": 2.0 * d * c.vocab_size,
    }


def step_forward_flops_per_token(c) -> dict:
    """The same by part, summed over the step's layers."""
    per = forward_flops_per_token(c)
    return {k: v * (1 if k == "head" else c.n_layer) for k, v in per.items()}


def train_flops_per_token(c) -> float:
    """Forward + backward: three times the forward (each matmul's
    backward is one pass for its input and one for its weights)."""
    return 3.0 * sum(step_forward_flops_per_token(c).values())


def flash_cores_train_cost(c, batch: int) -> dict:
    """Operations and HBM bytes of the attention cores for one training
    step, every layer's, as the kernel sees them: ``n_head`` heads of q,
    k and v each, the key/value heads already repeated
    (``flops.flash_attention_train_cost`` at equal widths)."""
    from benchlib import flops
    return flops.flash_attention_train_cost(
        batch, c.n_head, c.seq_len, c.head_dim, c.n_layer)


def held_experts_train_cost(c, tokens: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes of the held SwiGLU experts' grouped
    matmuls for one training step, every layer's, over the routes held
    at an even load (``tokens * held / E`` rows, top-1): three matrices
    (gate, up, down), each once forward and twice backward; each of
    those nine grouped matmuls reads its rows, reads or writes each
    held expert's matrix once and writes its result (``flops_moe``'s
    reckoning)."""
    rows = tokens * _held(c) / c.num_experts
    d, f = c.n_embd, c.expert_width
    per_matmul = rows * d + rows * f + _held(c) * d * f
    return {"flops": c.n_layer * 6.0 * rows * 3 * d * f,
            "bytes": c.n_layer * 9 * per_matmul * bytes_per_el}


def cca_mix_train_cost(c, tokens: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes that the passes between CCA's
    projections and its kernel need for one training step, every
    layer's (the scopes ``conv``, ``mix`` and ``rope``): forward they
    read the compressed ``[q~ | k~]`` (``latent`` values a token) once
    and write q and k for the kernel once; backward they read those
    two's cotangents and ``[q~ | k~]`` again (everything between is
    cheaper to make again than to keep) and write its cotangent: five
    passes of ``latent`` values a token. The convolutions' weights, the
    temperature and the angles are a few hundred kB and not counted;
    the operations are the convolution within heads (a [D, D] matmul a
    tap a head, once forward and twice backward); the element-wise
    work rides on the bytes."""
    taps, heads, hd = c.conv_taps[1], c.n_head + c.n_kv_head, c.head_dim
    return {"flops": c.n_layer * tokens * 3 * 2.0 * taps * heads * hd * hd,
            "bytes": c.n_layer * tokens * 5 * _latent(c) * bytes_per_el}

"""Operations, bytes and parameters of a Granite 4.0-H stack (Mamba-2
mixers at one group, NoPE grouped-query attention layers, a dense SwiGLU
MLP in every block, a tied table), from shapes alone. As in ``flops.py``:
required operations only, a multiply-add is two, recomputation does not
count. ``c`` is anything with the fields of
``ray_tpu.models.granite.GraniteHybridConfig`` (only its numbers are
read).
"""

from __future__ import annotations

# mamba_chunk_size as published: what the scan's required operations and
# kept states are reckoned at, whatever chunk or kernel a run takes
PUBLISHED_CHUNK = 256


def _inner(c) -> int:
    return c.mamba_heads * c.mamba_head_dim


def _conv(c) -> int:
    return _inner(c) + 2 * c.ssm_groups * c.ssm_state


def layer_params(c) -> dict:
    """Parameters by part: a ``mamba`` mixer (in_proj, the convolution's
    kernel and bias, dt_bias, A_log, D, the gate norm, out_proj), an
    ``attention`` mixer (q, k, v, o), the ``mlp`` (gate_up, down), a
    block's two ``norms``."""
    d, inner, conv = c.n_embd, _inner(c), _conv(c)
    hd = c.n_embd // c.n_head
    return {
        "mamba": (d * (inner + conv + c.mamba_heads)
                  + (c.conv_kernel + 1) * conv + 3 * c.mamba_heads + inner
                  + inner * d),
        "attention": 2 * d * c.n_head * hd + 2 * d * c.n_kv_head * hd,
        "mlp": 3 * d * c.mlp_width, "norms": 2 * d}


def num_params(c) -> int:
    """The blocks, the final norm and the tied table once."""
    per = layer_params(c)
    return (sum(per[k] for k in c.layer_types)
            + len(c.layer_types) * (per["mlp"] + per["norms"])
            + c.n_embd + c.vocab_size * c.n_embd)


def scan_forward_flops_per_token(c) -> float:
    """The chunked scan's four matmuls a token a layer, forward, at the
    published chunk: the chunk's score square **once a group** (chunk x
    N) and its product with x (a head: chunk x P), both needed only
    under the causal mask (half); the chunk's state (a head: P x N) and
    the carried state's output (the same)."""
    h, p, n, g = c.mamba_heads, c.mamba_head_dim, c.ssm_state, c.ssm_groups
    return (0.5 * 2 * g * PUBLISHED_CHUNK * n
            + 0.5 * 2 * h * PUBLISHED_CHUNK * p + 2 * 2 * h * p * n)


def forward_flops_per_token(c) -> dict:
    """Forward operations a token by part, all layers: 2 per matmul
    weight the token meets (``mamba_proj``: in_proj and out_proj;
    ``attn_proj``: q, k, v, o; ``mlp``; ``head``: the tied table as the
    head, the lookup is no matmul), the scans, causal attention (QK^T
    and PV over half the square)."""
    d, inner = c.n_embd, _inner(c)
    hd = c.n_embd // c.n_head
    mamba = sum(k == "mamba" for k in c.layer_types)
    attn = len(c.layer_types) - mamba
    return {
        "mamba_proj": mamba * (2.0 * d * (inner + _conv(c) + c.mamba_heads)
                               + 2.0 * inner * d),
        "mamba_scan": mamba * scan_forward_flops_per_token(c),
        "attn_proj": attn * 2.0 * (2 * d * c.n_head * hd
                                   + 2 * d * c.n_kv_head * hd),
        "attn_core": attn * 2 * 2.0 * c.seq_len * c.n_head * hd * 0.5,
        "mlp": len(c.layer_types) * 2.0 * 3 * d * c.mlp_width,
        "head": 2.0 * d * c.vocab_size}


def train_flops_per_token(c) -> float:
    """Forward + backward: three times the forward (each matmul's
    backward is one pass for its input and one for its weights)."""
    return 3.0 * sum(forward_flops_per_token(c).values())


def ssm_scan_train_cost(c, tokens: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes the selective scans of one training step
    need, all ``mamba`` layers, each operand once a pass, **``B`` and
    ``C`` once a group** (2 x 128 lanes a token here, where eight groups
    have 2,048): forward reads x, B, C (compute type) and dt (float32)
    and writes y; backward reads them and y's cotangent and writes the
    four cotangents; nothing kept between but the states entering the
    published chunks (float32, written forward, read backward). The same
    whatever chunk or kernel runs: what the kernels' eight head blocks a
    group read again, and the float32 shares of ``dB`` and ``dC`` they
    write, is theirs and not the algorithm's."""
    layers = sum(k == "mamba" for k in c.layer_types)
    inner = _inner(c)
    bc = 2 * c.ssm_groups * c.ssm_state
    row = (inner + bc) * bytes_per_el + c.mamba_heads * 4
    forward = row + inner * bytes_per_el
    backward = forward + inner * bytes_per_el + row
    boundary = (tokens / PUBLISHED_CHUNK * inner * c.ssm_state * 4) * 2
    return {"flops": layers * tokens * 3.0 * scan_forward_flops_per_token(c),
            "bytes": layers * (tokens * (forward + backward) + boundary)}


def flash_core_train_cost(c, rows: int) -> dict:
    """``flops.flash_attention_train_cost`` of the attention layers'
    cores at the query heads' count (the kernels read K and V repeated
    up to it)."""
    from benchlib import flops
    return flops.flash_attention_train_cost(
        rows, c.n_head, c.seq_len, c.n_embd // c.n_head,
        sum(k == "attention" for k in c.layer_types))

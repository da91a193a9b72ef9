"""Operations, bytes and parameters of a Nemotron-H stack (Mamba-2
layers, routed relu^2 experts with a shared expert, attention layers),
from shapes alone. As in ``flops.py``: required operations only, a
multiply-add is two, recomputation does not count. ``c`` is anything
with the fields of ``ray_tpu.models.nemotron_h.NemotronHConfig`` (only
its numbers are read).
"""

from __future__ import annotations


def _held(c) -> int:
    return c.experts_held[1] if c.experts_held else c.num_experts


def layer_params(c) -> dict:
    """Parameters of one layer of each kind, with its norm: ``M``
    (in_proj, conv weight and bias, dt_bias, A_log, D, the gate norm,
    out_proj), ``E`` (the held experts' two matrices, the shared
    expert's, the router and its bias), ``*`` (q, k, v, out)."""
    d = c.n_embd
    inner = c.mamba_heads * c.mamba_head_dim
    conv = inner + 2 * c.ssm_groups * c.ssm_state
    return {
        "M": (d * (inner + conv + c.mamba_heads) + (c.conv_kernel + 1) * conv
              + 3 * c.mamba_heads + inner + inner * d + d),
        "E": (_held(c) * 2 * d * c.expert_width + 2 * d * c.shared_width
              + d * c.num_experts + c.num_experts + d),
        "*": (d * (c.n_head + 2 * c.n_kv_head) * c.head_dim
              + c.n_head * c.head_dim * d + d),
    }


def num_params(c) -> int:
    per = layer_params(c)
    return (sum(per[k] for k in c.pattern) + 2 * c.vocab_size * c.n_embd
            + c.n_embd)


def scan_forward_flops_per_token(c) -> float:
    """The chunked scan's four matmuls a token, forward: the chunk's
    score square (a group: chunk x N) and its product with x (a head:
    chunk x P), both needed only under the causal mask (half); the
    chunk's state (a head: P x N) and the carried state's output (the
    same)."""
    h, p, n, g, chunk = (c.mamba_heads, c.mamba_head_dim, c.ssm_state,
                         c.ssm_groups, c.chunk)
    return (0.5 * 2 * g * chunk * n + 0.5 * 2 * h * chunk * p
            + 2 * 2 * h * p * n)


def forward_flops_per_token(c) -> dict:
    """Forward operations a token by part: 2 per matmul weight the
    token meets, the scan, causal attention (QK^T and PV over half the
    square), the routed experts at an even load (``top_k * held / E``
    routes a token land here)."""
    d = c.n_embd
    inner = c.mamba_heads * c.mamba_head_dim
    conv = inner + 2 * c.ssm_groups * c.ssm_state
    routes = c.top_k * _held(c) / c.num_experts
    return {
        "M": (2.0 * d * (inner + conv + c.mamba_heads) + 2.0 * inner * d
              + scan_forward_flops_per_token(c)),
        "E": (2.0 * 2 * d * c.shared_width
              + routes * 2.0 * 2 * d * c.expert_width
              + 2.0 * d * c.num_experts),
        "*": (2.0 * d * (2 * c.n_head + 2 * c.n_kv_head) * c.head_dim
              + 2 * 2.0 * c.seq_len * c.n_head * c.head_dim * 0.5),
        "head": 2.0 * d * c.vocab_size,
    }


def train_flops_per_token(c) -> float:
    """Forward + backward: three times the forward (each matmul's
    backward is one pass for its input and one for its weights)."""
    per = forward_flops_per_token(c)
    return 3.0 * (sum(per[k] for k in c.pattern) + per["head"])


def ssm_scan_train_cost(c, tokens: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes the selective scans of one training
    step need, all ``M`` layers. Bytes: forward reads x, B, C (compute
    type) and dt (float32) and writes y; backward reads them and y's
    cotangent and writes the four cotangents: twelve passes over a
    token's ``inner``-wide or ``2 G N``-wide rows, nothing kept between
    but the chunk-boundary states (float32, written forward, read
    backward)."""
    layers = c.pattern.count("M")
    inner = c.mamba_heads * c.mamba_head_dim
    bc = 2 * c.ssm_groups * c.ssm_state
    row = (inner + bc) * bytes_per_el + c.mamba_heads * 4
    forward = row + inner * bytes_per_el
    backward = forward + inner * bytes_per_el + row
    boundary = (tokens / c.chunk * inner * c.ssm_state * 4) * 2
    return {"flops": layers * tokens * 3.0 * scan_forward_flops_per_token(c),
            "bytes": layers * (tokens * (forward + backward) + boundary)}


def held_experts_train_cost(c, tokens: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes of the routed experts' grouped matmuls
    for one training step, all ``E`` layers, over the routes held at an
    even load (``tokens * top_k * held / E`` rows): two matrices (up,
    down), each once forward and twice backward; each of those six
    grouped matmuls reads its rows, reads or writes each held expert's
    matrix once and writes its result (``flops_moe``'s reckoning)."""
    layers = c.pattern.count("E")
    rows = tokens * c.top_k * _held(c) / c.num_experts
    d, f = c.n_embd, c.expert_width
    per_matmul = rows * d + rows * f + _held(c) * d * f
    return {"flops": layers * 6.0 * rows * 2 * d * f,
            "bytes": layers * 6 * per_matmul * bytes_per_el}

"""Operations, bytes and parameters of a decoder with routed experts
(OLMoE), from shapes alone. As in ``flops.py``: required operations
only, a multiply-add is two, recomputation does not count.
"""

from __future__ import annotations


def routed_decoder_params(n_layer: int, d: int, n_head: int, head_dim: int,
                          n_kv_head: int, num_experts: int,
                          expert_width: int, vocab_size: int) -> dict:
    """Parameters of one layer by part (attention with its two QK-norm
    scales, the stacked SwiGLU experts, the router, the two block
    norms), of one table (embedding or untied head), and of the whole
    model (``n_layer`` layers, both tables, the final norm)."""
    layer = {
        "attention": (2 * n_head + 2 * n_kv_head) * head_dim * d,
        "experts": num_experts * 3 * d * expert_width,
        "router": d * num_experts,
        "norms": 2 * d + n_head * head_dim + n_kv_head * head_dim,
    }
    table = vocab_size * d
    return {**layer, "layer": sum(layer.values()), "table": table,
            "total": n_layer * sum(layer.values()) + 2 * table + d}


def routed_decoder_train_flops_per_token(
        n_layer: int, d: int, n_head: int, head_dim: int, n_kv_head: int,
        num_experts: int, top_k: int, expert_width: int, seq_len: int,
        vocab_size: int) -> float:
    """Forward + backward operations per token: 6 per matmul weight the
    token meets (attention's four projections, ``top_k`` experts of
    three matrices, the router, the untied head — not the embedding
    lookup, not the norms) plus causal attention (QK^T and PV over half
    the square, and twice that backward)."""
    p = routed_decoder_params(n_layer, d, n_head, head_dim, n_kv_head,
                              num_experts, expert_width, vocab_size)
    per_layer = (p["attention"] + top_k * 3 * d * expert_width
                 + p["router"])
    attention = n_layer * 3 * (2 * 2 * seq_len * n_head * head_dim) * 0.5
    return 6.0 * (n_layer * per_layer + p["table"]) + attention


def grouped_matmul_train_cost(tokens: int, top_k: int, d: int,
                              expert_width: int, num_experts: int,
                              n_layer: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes the routed experts' grouped matmuls
    need for one training step: three matrices (gate, up, down) over
    ``tokens * top_k`` routed rows, each once forward and twice
    backward (for its input, for its weights). Bytes: every one of
    those nine grouped matmuls reads its rows, reads or writes each
    expert's matrix once, and writes its result — the routed
    activations in and out, each expert's weights once a pass."""
    rows = tokens * top_k
    flops = n_layer * 6.0 * rows * 3 * d * expert_width
    per_matmul = (rows * d + rows * expert_width
                  + num_experts * d * expert_width)
    return {"flops": flops,
            "bytes": n_layer * 9 * per_matmul * bytes_per_el}

"""Operations, bytes and parameters of a DeepSeek-V3-shaped stack
(latent attention, a leading dense layer, routed SwiGLU experts with a
shared one, a multi-token-prediction module), from shapes alone. As in
``flops.py``: required operations only, a multiply-add is two,
recomputation does not count (the up-projections that the backward pass
runs again are not in here). ``c`` is anything with the fields of
``ray_tpu.models.joyai.JoyAIConfig`` (only its numbers are read).
"""

from __future__ import annotations


def _held(c) -> int:
    return c.experts_held[1] if c.experts_held else c.num_experts


def _routed_blocks(c) -> int:
    """Blocks with a routed MLP: the main stack's and the MTP module's."""
    return c.n_layer - c.dense_layers + c.mtp_depth


def mla_matmul_weights(c) -> int:
    """Weights of latent attention's five projections (W_qa, W_qb,
    W_kva, W_kvb, W_o), the norms' scales left out."""
    d, h = c.n_embd, c.n_head
    return (d * c.q_rank + c.q_rank * h * (c.nope_dim + c.rope_dim)
            + d * (c.kv_rank + c.rope_dim)
            + c.kv_rank * h * (c.nope_dim + c.v_dim) + h * c.v_dim * d)


def layer_params(c) -> dict:
    """Parameters by part, as ``JoyAIConfig.layer_params``: ``mla``
    (with its two latent norms), a ``dense`` and a ``routed`` block
    (with the block's two norms; the router's bias counts), the ``mtp``
    module (a routed block, W_eh, three norms)."""
    d = c.n_embd
    mla = mla_matmul_weights(c) + c.q_rank + c.kv_rank
    routed = (mla + 2 * d + d * c.num_experts + c.num_experts
              + 3 * d * c.shared_width + _held(c) * 3 * d * c.expert_width)
    return {"mla": mla, "dense": mla + 2 * d + 3 * d * c.dense_width,
            "routed": routed, "mtp": routed + 2 * d * d + 3 * d}


def num_params(c) -> int:
    per = layer_params(c)
    return (c.dense_layers * per["dense"]
            + (c.n_layer - c.dense_layers) * per["routed"]
            + c.mtp_depth * per["mtp"] + 2 * c.vocab_size * c.n_embd
            + c.n_embd)


def forward_flops_per_token(c) -> dict:
    """Forward operations a token by part, one block or module each: 2
    per matmul weight the token meets; the attention core's QK^T (192
    wide) and PV (128 wide) over half the square; the routed experts at
    an even load (``top_k * held / E`` routes a token land here)."""
    d = c.n_embd
    routes = c.top_k * _held(c) / c.num_experts
    return {
        "mla_proj": 2.0 * mla_matmul_weights(c),
        "attn_core": (2.0 * c.seq_len * c.n_head
                      * (c.nope_dim + c.rope_dim + c.v_dim) * 0.5),
        "dense_mlp": 2.0 * 3 * d * c.dense_width,
        "shared": 2.0 * 3 * d * c.shared_width,
        "held_experts": routes * 2.0 * 3 * d * c.expert_width,
        "router": 2.0 * d * c.num_experts,
        "head": 2.0 * d * c.vocab_size,
        "mtp_proj": 2.0 * 2 * d * d,
    }


def step_forward_flops_per_token(c) -> dict:
    """The same by part, summed over the step's blocks: every block has
    attention, the dense ones the dense MLP, the routed ones (the MTP
    module's among them) the router, the shared and the held experts;
    the head runs once a loss (main and MTP)."""
    per = forward_flops_per_token(c)
    blocks = c.n_layer + c.mtp_depth
    routed = _routed_blocks(c)
    return {
        "mla_proj": blocks * per["mla_proj"],
        "attn_core": blocks * per["attn_core"],
        "dense_mlp": c.dense_layers * per["dense_mlp"],
        "shared": routed * per["shared"],
        "held_experts": routed * per["held_experts"],
        "router": routed * per["router"],
        "head": (1 + c.mtp_depth) * per["head"],
        "mtp_proj": c.mtp_depth * per["mtp_proj"],
    }


def train_flops_per_token(c) -> float:
    """Forward + backward: three times the forward (each matmul's
    backward is one pass for its input and one for its weights)."""
    return 3.0 * sum(step_forward_flops_per_token(c).values())


def latent_attention_train_cost(c, batch: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes that the attention cores need for one
    training step, every block's (``flops.flash_attention_train_cost``'s
    reckoning at unequal widths): forward QK^T (nope + rope wide) and PV
    (v wide), backward dV and dP (v wide), dQ and dK (nope + rope wide),
    each needed only under the causal mask (half). Bytes: forward reads
    q_nope, k_nope, v (a head each), q_rope, and the shared rotary key
    **once**, and writes o; backward reads those and o's cotangent and
    writes the five cotangents, the shared key's once; the per-row
    float32 statistics are counted too."""
    layers = c.n_layer + c.mtp_depth
    t, h = c.seq_len, c.n_head
    widths = 3 * (c.nope_dim + c.rope_dim) + 3 * c.v_dim
    flops = layers * batch * h * 2.0 * t * t * widths * 0.5
    wide = batch * t * h * c.nope_dim * bytes_per_el      # v_dim == nope_dim
    rope_q = batch * t * h * c.rope_dim * bytes_per_el
    shared_key = batch * t * c.rope_dim * bytes_per_el
    rows = batch * h * t * 4
    forward = 4 * wide + rope_q + shared_key + rows
    backward = 7 * wide + 2 * rope_q + 2 * shared_key + 2 * rows
    return {"flops": flops, "bytes": layers * (forward + backward)}


def held_experts_train_cost(c, tokens: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes of the held SwiGLU experts' grouped
    matmuls for one training step, every routed block's, over the
    routes held at an even load (``tokens * top_k * held / E`` rows):
    three matrices (gate, up, down), each once forward and twice
    backward; each of those nine grouped matmuls reads its rows, reads
    or writes each held expert's matrix once and writes its result
    (``flops_moe``'s reckoning)."""
    layers = _routed_blocks(c)
    rows = tokens * c.top_k * _held(c) / c.num_experts
    d, f = c.n_embd, c.expert_width
    per_matmul = rows * d + rows * f + _held(c) * d * f
    return {"flops": layers * 6.0 * rows * 3 * d * f,
            "bytes": layers * 9 * per_matmul * bytes_per_el}

"""Operations and bytes the algorithms need, from shapes alone.

Required operations only: what the forward and backward passes need,
not what the program happens to execute (a recomputed matmul, the
masked half of a causal score matrix and padding rows do not count).
A multiply-add is two operations.
"""

from __future__ import annotations


def gpt2_train_flops_per_token(n_layer: int, n_embd: int, seq_len: int,
                               vocab_size: int) -> float:
    """Forward + backward operations per token of a GPT-2 block stack
    with a tied LM head: 6 per matmul weight (2 forward, 4 backward;
    12*E^2 weights a block, V*E in the head — the embedding lookup,
    positions, biases and norms are not matmuls) plus causal attention
    (QK^T and PV: 2 * 2*T*E forward over the full square, half of it
    under the mask, and twice that backward)."""
    matmul_weights = n_layer * 12 * n_embd * n_embd + vocab_size * n_embd
    attention = n_layer * 3 * (2 * 2 * seq_len * n_embd) * 0.5
    return 6.0 * matmul_weights + attention


def conv_flops(h_out: int, w_out: int, c_in: int, c_out: int,
               k: int) -> float:
    return 2.0 * h_out * w_out * c_in * c_out * k * k


def resnet_forward_flops_per_image(stage_sizes, width: int,
                                   image_size: int,
                                   num_classes: int) -> float:
    """Forward operations of the bottleneck ResNet, convolutions and
    the classifier only (batch norm, activations and pooling are not
    counted): depths and widths of He et al. 2015, table 1, with
    stride 2 on the 3x3 convolution of each later stage's first block
    (v1.5, as in ``ray_tpu/models/resnet.py`` and torchvision). 224 x
    224 and 3-4-6-3 give 8.18e9, the 4.09e9 multiply-adds torchvision
    states for v1.5; the paper's table says 3.8e9 for its own variant,
    which strides on the first 1x1."""
    hw = image_size // 2                       # 7x7 stride 2
    total = conv_flops(hw, hw, 3, width, 7)
    hw //= 2                                   # 3x3 max pool stride 2
    c_in = width
    for i, n_blocks in enumerate(stage_sizes):
        f = width * 2 ** i
        for j in range(n_blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out_hw = hw // stride
            total += conv_flops(hw, hw, c_in, f, 1)
            total += conv_flops(out_hw, out_hw, f, f, 3)
            total += conv_flops(out_hw, out_hw, f, 4 * f, 1)
            if c_in != 4 * f or stride != 1:
                total += conv_flops(out_hw, out_hw, c_in, 4 * f, 1)
            hw, c_in = out_hw, 4 * f
    return total + 2.0 * c_in * num_classes


def resnet_train_flops_per_image(stage_sizes, width: int,
                                 image_size: int,
                                 num_classes: int) -> float:
    """Forward + backward: three times the forward (each convolution's
    backward is one pass for its input and one for its weights)."""
    return 3.0 * resnet_forward_flops_per_image(
        stage_sizes, width, image_size, num_classes)


def flash_attention_train_cost(batch: int, n_head: int, seq_len: int,
                               head_dim: int, n_layer: int,
                               bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes that causal attention needs for one
    training step of ``n_layer`` layers at [batch, seq, head, dim]:
    forward QK^T and PV, backward dV, dP, dQ and dK — six T x T x D
    matmuls, each needed only under the causal mask (half). The
    backward's recomputation of the scores is not counted. Bytes:
    forward reads q, k, v and writes o; backward reads q, k, v, o's
    cotangent and writes dq, dk, dv (the per-row f32 statistics are
    1/32 of that and are counted too)."""
    bh = batch * n_head
    per_matmul = 2.0 * seq_len * seq_len * head_dim * 0.5
    flops = n_layer * bh * 6 * per_matmul
    tensor = bh * seq_len * head_dim * bytes_per_el
    rows = bh * seq_len * 4
    bytes_moved = n_layer * ((4 * tensor + rows) + (7 * tensor + 2 * rows))
    return {"flops": flops, "bytes": bytes_moved}


def roofline(flops: float, bytes_moved: float, peak_flops: float,
             peak_bytes_per_s: float) -> dict:
    """Least time the chip could take and which peak sets it."""
    t_compute = flops / peak_flops
    t_memory = bytes_moved / peak_bytes_per_s
    return {"least_s": max(t_compute, t_memory),
            "bound": "compute" if t_compute >= t_memory else "memory"}

"""What a recomputed block keeps.

A model whose config says ``remat`` wraps each block in ``nn.remat``: the
backward pass makes the block's forward again from its input. Made again
*whole* it would run every forward kernel and every matmul twice; so a
policy keeps, by name, the few arrays that are dear to make and cheap to
hold (``save_only_these_names``). This file is that mechanism's one home:
**the names**, every one, by who makes the array (a name is the identity
outside a policy that lists it, and **a policy that lists a name no value
of the block carries keeps nothing for it, silently**:
``tests/test_remat_keeps.py`` holds every model's list to its traced
block); **the policy** (``remat_policy``: a model's names and the
attention cores' two); **the block** (``block``: ``Block`` itself where
the config does not recompute, else ``nn.remat`` under layer ``i``'s
policy); **the per-layer choice** (``keeps`` is a tuple of names every
layer keeps, or ``{name: the first layer that keeps it}`` where memory
ends the list layer by layer); **the note** (``keeps_note``: what a model
says on its trace span as ``blocks_remat_keeps``).

Which names a model lists is its own literal, settled by its cell's
memory, dearest millisecond a byte first (docs/training_perf.md): no code
reads the byte counts below. It imports ``jax`` and ``flax`` and nothing
of the repo, so that the kernels' files can import their names from it.
"""

from __future__ import annotations

import flax.linen as nn
import jax
from jax.ad_checkpoint import checkpoint_name

# -- the names, by producer --------------------------------------------------
# Each: who names it | which backward reads it | bytes a token a layer.

# The flash cores' forward rules (``ops/pallas/flash_attention.py``, both
# the equal-width and the latent core; ``ops/mla.py``'s own rule), through
# ``name_core_results``:
ATTN_OUT = "attn_out"    # the core's output | its backward kernel | 2 H D
ATTN_LSE = "attn_lse"    # the rows' log-sum-exp, which only the forward
#                          kernel makes | the same | 4 H

# A dense MLP's matmul products (``models/llama.py::SwiGLU``: three, so
# that a model can keep a subset; ``models/phi4flash.py::MLP``: ``[g | u]``
# before the split):
MLP_GATE = "mlp_gate"        # x W_g | silu's and the product's | 2 f
MLP_UP = "mlp_up"            # x W_u | the product's | 2 f
MLP_DOWN = "mlp_down"        # the MLP's output | the next norm's | 2 d
MLP_GATE_UP = "mlp_gate_up"  # x [W_g | W_u] | the split's | 4 f

# A router (``ops/moe.py::_logits``; the choice's kernel pair,
# ``ops/pallas/router_choice.py``'s forward rule, or ``_route_sigmoid``'s
# XLA lines and ``_routed_ffn_local``'s scatter-add): 17 MB a layer at
# 16,384 rows of 256 experts, 35 MB at 512.
ROUTER_LOGITS = "moe_router_logits"    # x W_r float32, in front of the
#   sigmoid, not behind it: the sigmoid's backward reads the sigmoid's own
#   result, so a name behind it would be kept and never read | the scores
#   are made again from it (in the backward kernel's VMEM, or one fused
#   pass), the float32 matmul at the highest precision is not | 4 E
ROUTER_EXPERTS = "moe_router_experts"  # the chosen experts | dispatch | 4 k
ROUTER_WEIGHTS = "moe_router_weights"  # their scores | combine | 4 k
ROUTER_COUNTS = "moe_router_counts"    # routes an expert received, [E] |
#   the grouped matmuls' sizes | nothing a token
ROUTER_LSE = "moe_router_lse"          # the softmax router's logsumexp,
#   which only the kernel's forward makes | its backward kernel | 4
ROUTER_KEEPS = (ROUTER_LOGITS, ROUTER_EXPERTS, ROUTER_WEIGHTS,
                ROUTER_COUNTS, ROUTER_LSE)
# and the routed sum a layer returns (``models/joyai.py::MoE``, in front
# of the shared expert's):
MOE_OUT = "moe_routed_out"   # the held experts' part of every token's sum
#   | under residual maps ``post``'s backward (the sum with the shared
#   expert's is one add); under ``x + f(x)`` nobody's | 2 d

# The Mamba-2 scan's kernels (``ops/pallas/ssd_scan.py``'s forward rule):
SSD_SCAN_OUT = "ssd_scan_out"        # y | the gated norm's | 2 H P
SSD_SCAN_STATES = "ssd_scan_states"  # the state entering each chunk |
#   the backward kernel | 4 H P N / chunk (67 MB each a layer at 8,192
#   rows of 64 x 64, state 128, chunk 256)
# and its mixer's ``in_proj`` product, named in its three parts
# (``models/nemotron_h.py::Mamba2Mixer``):
IN_PROJ_PARTS = ("mamba_z", "mamba_xbc", "mamba_dt")    # 2 (2 H P + 2 G N
#   + H) | the norm's, the convolution's, softplus's

# The Mamba-1 scan's kernels (``ops/pallas/mamba1_scan.py``'s forward
# rule):
MAMBA1_SCAN_OUT = "mamba1_scan_out"        # y float32 | the gate's, the
#   memory's | 4 C
MAMBA1_SCAN_STATES = "mamba1_scan_states"  # the state entering each row
#   block | the backward kernel | 4 C N / 64 (21 MB a layer at 4,096 rows
#   of 5,120 channels, state 16)

# The gated delta rule's kernels (``ops/pallas/kda_scan.py``'s forward
# rules: Kimi Delta Attention's and Gated DeltaNet's):
KDA_SCAN_OUT = "kda_scan_out"        # o float32 | the output gate's | 4 H V
KDA_SCAN_STATES = "kda_scan_states"  # the state entering each chunk |
#   the backward kernel | 4 H K V / 64 (268 + 537 MB a layer at 16,384
#   rows of 32 heads)

# mHC's residual maps (``ops/pallas/hc_maps.py``'s forward rule): the
# kernel's three results, and the product and the norm's factor its
# backward reads; 41 floats a token a sub-layer at n = 4.
MAPS_PRE = "hc_maps_pre"
MAPS_POST = "hc_maps_post"
MAPS_RES = "hc_maps_res"
MAPS_M = "hc_maps_m"
MAPS_R = "hc_maps_r"
MAPS_KEEPS = (MAPS_PRE, MAPS_POST, MAPS_RES, MAPS_M, MAPS_R)

# The mixers' own (the model files name them where they make them):
ATTN_Q = "attn_q"    # ``models/laguna.py::Attention``: q, k as the rotation
ATTN_K = "attn_k"    #   left them (full layer) or the products did
ATTN_V = "attn_v"    #   (sliding), and v | the core's backward | 2 H D,
#                        2 x 2 G D
#   ``models/phi4flash.py::DiffAttention``: the three products with their
#   biases | ``repeat``, which writes the core's operands out of them
ATTN_PROJ = "attn_out_proj"     # laguna: W_o's product | the stream's
#                                 add | 2 d (67 MB at 16,384 rows)
MIXER_PROJ = "mixer_out_proj"   # ``models/qwen3_next.py``: either mixer's
#   output projection's product | the stream's add; ``models/joyai.py``:
#   the same, which under residual maps ``post``'s backward reads | 2 d
MIXER_IN = "mixer_in_proj"      # a mixer's wide input projections'
#   products as the matmuls left them. phi4flash: a Mamba mixer's ``[x |
#   z]`` and the gated memory unit's ``W_in h`` | the convolution's, the
#   gates' | 4 C, 2 C; kimi_linear: a KDA mixer's ``W_q h``, ``W_k h``,
#   ``W_v h`` | the convolutions' | 3 x 2 H K (134 MB each a layer at
#   16,384 rows)
MIXER_STREAM = "mixer_stream"   # ``models/granite.py``,
#   ``models/kimi_linear.py``, ``models/phi4flash.py``: the stream after
#   the mixer | the MLP's norm | 2 d (kept, the mixer's output projection
#   has no reader left in the second pass; against ``MIXER_PROJ`` it spares
#   the forward pass a product written beside the sum and the second pass
#   the add)
KDA_OUT = "kda_gated_out"       # ``models/kimi_linear.py``: the output
#                                 gate's result | W_o's | 2 H V
GDN_OUT = "gdn_gated_out"       # qwen3_next: the same behind Gated
#                                 DeltaNet | 2 H V (134 MB)
GDN_IN = "gdn_in_proj"          # qwen3_next: the mixer's two input
#                                 products | the convolution's, the
#                                 decay's | 2 (2 Hk K + 2 H V + 2 H)


def name_core_results(out, lse):
    """A forward kernel's two results under the names a recomputed
    block's policy keeps (``remat_policy``), so that its backward pass
    does not run the kernel again for them. For the forward rule of a
    core's ``custom_vjp`` (``ops/pallas/flash_attention.py``,
    ``ops/mla.py``), before the two part into primal and residuals: a
    name on the primal alone would leave ``lse`` to be made again, and
    the kernel with it."""
    return checkpoint_name(out, ATTN_OUT), checkpoint_name(lse, ATTN_LSE)


def remat_keeps(*more: str) -> tuple[str, ...]:
    """The names a recomputed block keeps: a model's own (``more``)
    first, then the attention cores' two."""
    return (*more, ATTN_OUT, ATTN_LSE)


def remat_policy(*more: str):
    """``nn.remat`` / ``jax.checkpoint``'s policy for a block that holds
    an attention core: everything is made again in the backward pass but
    what carries one of ``remat_keeps(*more)``. So q, k and v are
    projected again and the forward kernel, the dearest thing in the
    block a byte kept, is not run again for an ``out`` and an ``lse``
    the first pass made (docs/training_perf.md). The names are the
    identity outside such a policy."""
    return jax.checkpoint_policies.save_only_these_names(
        *remat_keeps(*more))


def _first_layers(keeps) -> dict[str, int]:
    return dict(keeps) if isinstance(keeps, dict) else dict.fromkeys(keeps, 0)


def layer_keeps(keeps, i: int) -> tuple[str, ...]:
    """The names layer ``i``'s policy lists beside the cores' two, from
    ``keeps``: a tuple of names (every layer keeps each) or ``{name:
    the first layer that keeps it}``; the layers before that one make
    the array again in the backward pass."""
    return tuple(n for n, first in _first_layers(keeps).items() if i >= first)


def keeps_note(on: bool, keeps=()) -> str:
    """``blocks_remat_keeps``: the names of ``keeps`` in its order, a
    name that the layers from ``k`` > 0 on alone keep as ``name[k:]``,
    the cores' two last; empty where the blocks are not recomputed."""
    if not on:
        return ""
    return ",".join(remat_keeps(*(
        f"{n}[{first}:]" if first else n
        for n, first in _first_layers(keeps).items())))


def block(cls, on: bool, keeps=(), i: int = 0, **kw):
    """The block class layer ``i`` is built from: ``cls`` itself where
    the config does not recompute (``on`` false), else ``nn.remat(cls)``
    under ``remat_policy(*layer_keeps(keeps, i))``; ``kw`` are
    ``nn.remat``'s own (``static_argnums``)."""
    if not on:
        return cls
    return nn.remat(cls, policy=remat_policy(*layer_keeps(keeps, i)), **kw)

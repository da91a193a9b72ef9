"""Operations, bytes and parameters of a DeepSeek-V3-shaped stack under
manifold-constrained hyper-connections (``n`` residual streams mixed by
learned maps round every attention and MLP), from shapes alone. The
stack's own parts are ``flops_mla.py``'s, which reads the same fields;
this file adds the residual path's: ``hc_maps`` (the one matmul of a
sub-layer, ``[n d] x [n d, n^2 + 2n]``) and ``hc_mix`` (the read of the
streams into a sub-layer's input and the write-back, ``n`` and ``n^2 +
n`` multiply-adds a lane). As in ``flops.py``: required operations only,
a multiply-add is two, recomputation does not count. ``c`` is anything
with the fields of ``ray_tpu.models.joyai.JoyAIConfig`` (only its
numbers are read).
"""

from __future__ import annotations

from benchlib import flops_mla

latent_attention_train_cost = flops_mla.latent_attention_train_cost
held_experts_train_cost = flops_mla.held_experts_train_cost


def _sub_layers(c) -> int:
    """Sub-layers under the residual maps: two a block, the MTP
    module's block among them; none at ``hc_mult`` 1."""
    return 2 * (c.n_layer + c.mtp_depth) if c.hc_mult > 1 else 0


def map_width(c) -> int:
    return c.hc_mult * c.hc_mult + 2 * c.hc_mult


def hc_params_per_sub_layer(c) -> int:
    """``phi``, ``b`` and the three gates."""
    return (c.hc_mult * c.n_embd + 1) * map_width(c) + 3


def layer_params(c) -> dict:
    """``flops_mla.layer_params`` with each block's two sets of maps."""
    per = flops_mla.layer_params(c)
    maps = 2 * hc_params_per_sub_layer(c) if c.hc_mult > 1 else 0
    return {"mla": per["mla"], "hc": maps,
            **{k: per[k] + maps for k in ("dense", "routed", "mtp")}}


def num_params(c) -> int:
    per = layer_params(c)
    return (c.dense_layers * per["dense"]
            + (c.n_layer - c.dense_layers) * per["routed"]
            + c.mtp_depth * per["mtp"] + 2 * c.vocab_size * c.n_embd
            + c.n_embd)


def step_forward_flops_per_token(c) -> dict:
    """``flops_mla``'s parts summed over the step's blocks, plus the
    residual path's two over its sub-layers: the maps' matmul, 2 a
    weight; the mixes, ``n`` multiply-adds a lane into the sub-layer's
    input and ``n^2 + n`` back. The Sinkhorn loop (about ``iters x 4
    n^2`` operations a token on the VPU) is not counted: no MXU runs
    it."""
    parts = flops_mla.step_forward_flops_per_token(c)
    n, d, subs = c.hc_mult, c.n_embd, _sub_layers(c)
    parts["hc_maps"] = subs * 2.0 * n * d * map_width(c)
    parts["hc_mix"] = subs * 2.0 * d * (n + n * n + n)
    return parts


def train_flops_per_token(c) -> float:
    """Forward + backward: three times the forward."""
    return 3.0 * sum(step_forward_flops_per_token(c).values())


def hc_train_cost(c, tokens: int, bytes_per_el: int = 2) -> dict:
    """Operations and **HBM bytes the residual path needs whatever
    implements it**, one training step, forward once and backward once,
    the state in its stated type (``bytes_per_el``). A sub-layer
    forward reads the state for the maps and ``pre`` once (``n d``),
    writes ``u`` (``d``), reads the state and ``y`` and writes the new
    state (``2 n d + d``); backward reads the state, ``y`` and the new
    state's cotangent and writes the state's and ``y``'s (``3 n d + 2
    d``), plus ``u``'s cotangent (``d``): ``(6 n + 5) d`` elements a
    token a sub-layer. What a recomputed block reads again is not
    counted, nor the maps themselves (24 floats a token)."""
    n, d, subs = c.hc_mult, c.n_embd, _sub_layers(c)
    per = step_forward_flops_per_token(c)
    return {"flops": 3.0 * tokens * (per["hc_maps"] + per["hc_mix"]),
            "bytes": subs * tokens * (6 * n + 5) * d * bytes_per_el}

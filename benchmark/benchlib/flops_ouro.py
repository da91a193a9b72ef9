"""Operations, bytes and parameters of an Ouro-shaped stack (``n_layer``
four-norm blocks run ``ut_steps`` times on one parameter tree, an exit
gate and the untied head after every pass), from shapes alone. As in
``flops.py``: required operations only, a multiply-add is two,
recomputation does not count. **Applications are counted, not
parameters**: a block's weights meet a token ``ut_steps`` times a step
and the head's ``ut_steps`` times, so "6 x parameters x tokens" would
undercount the cell 3.3 times (612 M parameters, 2.05 G matmul
parameter-applications a token). ``c`` is anything with the fields of
``ray_tpu.models.ouro.OuroConfig`` (only its numbers are read).
"""

from __future__ import annotations

from benchlib import flops


def layer_params(c) -> dict:
    """Parameters of a layer by part, as ``OuroConfig.layer_params``:
    ``attn`` (W_q, W_k, W_v, W_o), ``mlp`` (gate, up, down), ``norms``
    (four scales)."""
    d, hd = c.n_embd, c.head_dim
    return {"attn": 2 * d * c.n_head * hd + 2 * d * c.n_kv_head * hd,
            "mlp": 3 * d * c.intermediate, "norms": 4 * d}


def num_params(c) -> int:
    """``n_layer`` blocks whatever ``ut_steps``, the two tables, the final
    norm, the gate and its bias."""
    return (c.n_layer * sum(layer_params(c).values())
            + 2 * c.vocab_size * c.n_embd + c.n_embd + c.n_embd + 1)


def applications(c) -> dict:
    """How often a step applies what: every block and its core
    ``ut_steps`` times, the head, the final norm and the gate once a
    pass."""
    return {"blocks": c.ut_steps * c.n_layer,
            "cores": c.ut_steps * c.n_layer, "heads": c.ut_steps}


def step_forward_flops_per_token(c) -> dict:
    """Forward operations a token by part, over all the passes: 2 per
    matmul weight each time the token meets it; a core's QK^T and PV over
    half the square (``flops.flash_attention_train_cost``'s convention);
    the head and the gate once a pass."""
    per = layer_params(c)
    n = applications(c)
    return {"attn_proj": n["blocks"] * 2.0 * per["attn"],
            "mlp": n["blocks"] * 2.0 * per["mlp"],
            "core": n["cores"] * 2.0 * c.n_head * c.head_dim * c.seq_len,
            "head": n["heads"] * 2.0 * c.n_embd * c.vocab_size,
            "gate": n["heads"] * 2.0 * c.n_embd}


def train_flops_per_token(c) -> float:
    """Forward + backward: three times the forward (each matmul's
    backward is one pass for its input and one for its weights)."""
    return 3.0 * sum(step_forward_flops_per_token(c).values())


def flash_cores_train_cost(c, batch: int) -> dict:
    """Operations and HBM bytes of the attention cores of one training
    step as the equal-width kernel sees them: ``ut_steps x n_layer``
    calls of ``flops.flash_attention_train_cost``'s one layer (what the
    custom calls under ``attn`` have to do: ``attn_flash_roofline``)."""
    return flops.flash_attention_train_cost(
        batch, c.n_head, c.seq_len, c.head_dim, applications(c)["cores"])


def norms_train_cost(c, tokens: int, bytes_per_el: int = 2) -> dict:
    """What the norms of one training step have to move, were each one
    pass: four a block application and the final norm a pass, forward
    (the row in, the row out) and backward (the row and the cotangent in,
    the cotangent out); about 5 operations an element a direction."""
    calls = 4 * applications(c)["blocks"] + c.ut_steps
    row = tokens * c.n_embd
    return {"flops": calls * 3 * 5.0 * row,
            "bytes": calls * 5 * row * bytes_per_el}

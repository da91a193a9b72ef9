"""Operations, bytes and parameters of a Phi-4-mini-flash-shaped stack
(Mamba-1 scans, differential attention under a window and full, gated
memory units, cross attention on another layer's K and V, dense SwiGLU
MLPs, a tied table), from shapes alone. As in ``flops.py``: required
operations only, a multiply-add is two, recomputation does not count.
**A differential core is counted as two score maps**, 64-wide keys
against 128-wide values, whatever products a program makes of them (the
four equal-width ones of ``ops/attention.py::differential_attention``
make each map twice), and **the band is counted, not the triangle**
(``flops_smallthinker.seen_entries``). ``c`` is anything with the fields
of ``ray_tpu.models.phi4flash.Phi4FlashConfig`` (only its numbers are
read).
"""

from __future__ import annotations

from benchlib.flops_smallthinker import seen_entries


def kind_of(c, layer: int) -> str:
    """``M``, ``S``, ``F``, ``G`` or ``X``: the architecture's rule, as
    ``references/phi4flash.py::kind_of`` has it."""
    half = c.n_layer // 2
    if layer % 2 == 0:
        return "M" if layer <= half else "G"
    if layer < half:
        return "S"
    return "F" if layer == half + 1 else "X"


def layers_of(c) -> dict:
    """How many layers of each kind the stack has."""
    kinds = [kind_of(c, i) for i in range(c.n_layer)]
    return {k: kinds.count(k) for k in "MSFGX"}


def layer_params(c) -> dict:
    """Parameters by part, as ``Phi4FlashConfig.layer_params``."""
    d, inner, n, r = c.n_embd, c.mamba_inner, c.ssm_state, c.dt_rank
    q, kv = c.n_head * c.head_dim, c.n_kv_head * c.head_dim
    diff = 4 * c.head_dim + 2 * c.head_dim      # four lambdas, the pair norm
    attn = (d + 1) * (q + 2 * kv) + diff + (q + 1) * d
    return {
        "M": (d * 2 * inner + (c.conv_kernel + 1) * inner
              + inner * (r + 2 * n) + (r + 1) * inner + inner * n + inner
              + inner * d),
        "S": attn, "F": attn,
        "G": 2 * d * inner,
        "X": (d + 1) * q + diff + (q + 1) * d,
        "mlp": 3 * d * c.mlp_width,
        "norms": 4 * d}


def num_params(c) -> int:
    per, kinds = layer_params(c), layers_of(c)
    return (sum(per[k] * n for k, n in kinds.items())
            + c.n_layer * (per["mlp"] + per["norms"])
            + 2 * c.n_embd + c.vocab_size * c.n_embd)


def scan_forward_flops_per_token(c) -> float:
    """Elementwise operations a token of one layer's selective scan: a
    state entry's ``dt * A``, its exponential, the decay's product with
    the state, the write's product with ``B``, the sum, the product with
    ``C`` and the sum over the states (7); a channel's ``dt * x``, ``D *
    x`` and the skip's sum (3)."""
    return 7.0 * c.mamba_inner * c.ssm_state + 3.0 * c.mamba_inner


def _core_flops_per_seen_entry(c) -> float:
    """Forward operations of a differential core for one (row, key)
    entry the mask lets through, every query pair: two maps, each a
    ``D``-wide score and a ``2D``-wide product with ``[v1 | v2]``."""
    return (c.n_head // 2) * 2 * (2.0 * c.head_dim + 2.0 * 2 * c.head_dim)


def forward_flops_per_token(c) -> dict:
    """Forward operations a token by part, one layer each (``head`` once
    a step): 2 per matmul weight the token meets; a scan's elementwise
    operations; a core over the keys a row sees on average."""
    d, inner, n, r = c.n_embd, c.mamba_inner, c.ssm_state, c.dt_rank
    q, kv = c.n_head * c.head_dim, c.n_kv_head * c.head_dim
    t = c.seq_len
    per_entry = _core_flops_per_seen_entry(c)
    return {
        "mamba_proj": 2.0 * (d * 2 * inner + inner * (r + 2 * n) + r * inner
                             + inner * d),
        "mamba_scan": scan_forward_flops_per_token(c),
        "attn_proj": 2.0 * (d * (q + 2 * kv) + q * d),
        "cross_proj": 2.0 * 2 * q * d,
        "core_window": per_entry * seen_entries(t, c.window) / t,
        "core_full": per_entry * seen_entries(t, None) / t,
        "gmu": 2.0 * 2 * d * inner,
        "mlp": 2.0 * 3 * d * c.mlp_width,
        "head": 2.0 * d * c.vocab_size,
    }


def step_forward_flops_per_token(c) -> dict:
    """The same by part, summed over the step's layers."""
    per, k = forward_flops_per_token(c), layers_of(c)
    times = {"mamba_proj": k["M"], "mamba_scan": k["M"],
             "attn_proj": k["S"] + k["F"], "cross_proj": k["X"],
             "core_window": k["S"], "core_full": k["F"] + k["X"],
             "gmu": k["G"], "mlp": c.n_layer, "head": 1}
    return {name: v * times[name] for name, v in per.items()}


def train_flops_per_token(c) -> float:
    """Forward + backward: three times the forward."""
    return 3.0 * sum(step_forward_flops_per_token(c).values())


def _cores_train_cost(c, batch: int, layers: int, window: int | None,
                      bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes that ``layers`` differential cores need
    for one training step: the two maps' six matmuls each (QK^T, PV; dV,
    dP, dQ, dK) over the entries the mask lets through; forward reads
    q, k, v as the projections wrote them (40 and 20 heads of 64: no
    copy of a head is counted) and writes the pairs' 128-wide results,
    backward reads q, k, v, o, dO and writes dq, dk, dv, each once, and
    a row's float32 statistics a map."""
    flops = (layers * batch * 3.0 * _core_flops_per_seen_entry(c)
             * seen_entries(c.seq_len, window))
    rows = batch * c.seq_len
    wide = rows * c.n_head * c.head_dim * bytes_per_el          # q, o
    narrow = rows * c.n_kv_head * c.head_dim * bytes_per_el     # k, v
    stats = rows * c.n_head * 4
    return {"flops": flops,
            "bytes": layers * ((2 * wide + 2 * narrow + stats)
                               + (4 * wide + 4 * narrow + 2 * stats))}


def window_cores_train_cost(c, batch: int) -> dict:
    """The ``S`` layers' cores: the band, exactly."""
    return _cores_train_cost(c, batch, layers_of(c)["S"], c.window)


def flash_cores_train_cost(c, batch: int) -> dict:
    """Every attention layer's core (``S``, ``F``, ``X``): what the
    custom calls under ``attn`` have to do (``attn_flash_roofline``)."""
    k = layers_of(c)
    parts = (window_cores_train_cost(c, batch),
             _cores_train_cost(c, batch, k["F"] + k["X"], None))
    return {name: sum(p[name] for p in parts) for name in ("flops", "bytes")}


def ssm_scan_train_cost(c, batch: int, bytes_per_el: int = 2) -> dict:
    """Operations and HBM bytes the selective scans of one training
    step need, all ``M`` layers: the elementwise operations forward and
    twice that backward; forward reads x, B, C (compute type) and dt
    (float32) and writes y; backward reads them and y's cotangent and
    writes the four cotangents, each once. The states a chunk hands the
    next are an implementation's own (a kernel's VMEM; 335 MB a layer
    of HBM at this program's chunks of 4 rows) and are not counted."""
    layers = layers_of(c)["M"]
    tokens = batch * c.seq_len
    inner, n = c.mamba_inner, c.ssm_state
    row = inner * bytes_per_el + inner * 4 + 2 * n * bytes_per_el
    forward = row + inner * bytes_per_el
    backward = forward + row
    return {"flops": layers * tokens * 3.0 * scan_forward_flops_per_token(c),
            "bytes": layers * tokens * (forward + backward)}

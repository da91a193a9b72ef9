"""The depthwise causal convolution over time with its SiLU, in front of
a recurrence: Mamba-2's and Mamba-1's (``models/nemotron_h.py``,
``phi4flash.py``: 4 taps with a bias) and the gated delta rule's
(``kimi_linear.py``: three a layer, no bias; ``qwen3_next.py``: one).

``conv_path()`` gives it its own two kernels
(``ops/pallas/causal_conv.py``, ``pallas``) on a TPU where ``x`` is
``[b, T, C]`` with ``C`` whole 128-lane tiles, a row reads no more rows
before itself than one sublane tile holds, and the program is one
device's or shards the batch alone (``program.batch_axes``): each pass
reads its operands once, the rows before a block through a second view
of ``x`` and the rows after it carried in VMEM, float32 inside from the
operands' own dtype. As XLA's fusions over a padded copy the forward ran
at 2.9 times its bytes and the backward at 5.7 (PERF.md section 6, PRs
47 and 55). Everywhere else (the CPU, the tiny presets' widths, ``sp`` /
``tp``) the XLA function runs (``xla``), in the compute type, and is
what the kernels are tested against. Both keep ``x`` for the backward
and nothing else. Every call notes ``conv_path``, ``conv_taps`` and
``conv_cols`` for the trace in progress.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas import causal_conv, program
from ray_tpu.util import tracing


def conv_path(shape, taps: int, mesh=None) -> str:
    """Which convolution ``causal_conv1d_silu`` compiles for ``x``
    [b, T, C] at ``taps`` taps: ``pallas`` (the kernels of
    ``ops/pallas/causal_conv.py``) on a TPU where ``C`` is whole
    128-lane tiles, the rows a row reads before itself fit one sublane
    tile and ``program.batch_axes`` finds the program one the kernels
    can serve, else ``xla``."""
    if (jax.default_backend() == "tpu" and len(shape) == 3
            and causal_conv.shapes_ok(shape[-1], taps)
            and program.batch_axes(mesh, shape[0]) is not None):
        return "pallas"
    return "xla"


def causal_conv1d_silu(x, weight, bias=None, *, mesh=None):
    """``silu`` of the depthwise causal convolution over time: ``y[t,
    c] = bias[c] + sum_j weight[j, c] * x[t - (K - 1) + j, c]``, zeros
    before the start. x [batch, T, C]; weight [K, C]; bias [C], or None
    for a convolution without one (Kimi Delta Attention's three).
    Recomputed in the backward: only ``x`` is kept, not the sum in
    front of the SiLU. ``mesh`` is the mesh the program is sharded
    over, if the caller knows one: ``conv_path`` decides from it
    between the kernels (``ops/pallas/causal_conv.py``, float32 inside)
    and the XLA function below (the compute type throughout)."""
    path = conv_path(x.shape, weight.shape[0], mesh)
    tracing.note_trace(conv_path=path, conv_taps=weight.shape[0],
                       conv_cols=x.shape[-1])
    if path == "pallas":
        return causal_conv.causal_conv(
            x, weight, bias, mesh=mesh,
            batch_axes=program.batch_axes(mesh, x.shape[0]))
    return _causal_conv1d_silu_xla(x, weight, bias)


@jax.checkpoint
def _causal_conv1d_silu_xla(x, weight, bias=None):
    """K shifted multiply-adds (K is 4) over a padded copy of ``x`` and
    a SiLU."""
    K = weight.shape[0]
    T = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    w = weight.astype(x.dtype)
    y = 0 if bias is None else bias.astype(x.dtype)
    for j in range(K):
        y = y + padded[:, j:j + T] * w[j]
    return jax.nn.silu(y)

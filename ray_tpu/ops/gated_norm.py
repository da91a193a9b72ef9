"""The gated RMSNorms behind a recurrence, recomputed in the backward
on either path.

``gated_group_rms_norm`` is Mamba-2's output norm, ``RMSNorm(y *
silu(z))`` in groups (``models/nemotron_h.py::Mamba2Mixer``).
``norm_path()`` gives it its own two kernels
(``ops/pallas/gated_norm.py``, ``pallas``) on a TPU where each of its
groups is whole 128-lane tiles and the program is one the scan's kernels
serve (``program.batch_axes``), so that the norm stays in the row-major
``[B, T, H*P]`` the scan's kernel writes and ``out_proj``'s matmul
reads; behind a custom call the XLA function's reshape to ``[.., groups,
C / groups]`` was a relayout of a float32 array three times a layer.
Everywhere else that XLA function runs (``xla``).

``sigmoid_gated_head_rms_norm`` is Kimi Delta Attention's output gate
(``models/kimi_linear.py``), which stands where that norm does behind
another recurrence: a sigmoid on the *normed* output, where Mamba-2
norms the gated one; ``scale`` one head's width, shared by the heads. It
decides by the same ``norm_path()`` with a head a group: ``pallas`` is
the second kernel pair of ``ops/pallas/gated_norm.py``
(``head_gate_norm``), which reads the recurrence's float32 ``o`` and the
bfloat16 ``gate`` once a pass in the row-major ``[B, T, H*K]`` that the
recurrence's kernel writes and ``W_o``'s matmul reads; ``xla`` the
reshape-and-mean function under a ``jax.checkpoint``, the CPU's path and
what the tests hold the kernels to (41.2 ms of the Kimi-Linear cell's
step as that function: PERF.md section 6, PR 58). Every call notes
``kda_gate_path``; with ``gate_fn="silu"`` it is Gated DeltaNet's gate
(``models/qwen3_next.py``) and the note is ``gdn_gate_path``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.pallas import gated_norm, program
from ray_tpu.util import tracing


def norm_path(shape, groups: int, mesh=None) -> str:
    """Which gated norm ``gated_group_rms_norm`` compiles for ``y``
    [b, T, C] in ``groups`` groups, and ``sigmoid_gated_head_rms_norm``
    for ``o`` [b, T, C] in as many heads: ``pallas`` exactly where the
    scan takes its kernels (a TPU, each group whole 128-lane tiles, and
    ``program.batch_axes`` finds the program one the kernels can
    serve), else ``xla``."""
    if (jax.default_backend() == "tpu" and len(shape) == 3
            and gated_norm.shapes_ok(shape[-1], groups)
            and program.batch_axes(mesh, shape[0]) is not None):
        return "pallas"
    return "xla"


def gated_group_rms_norm(y, z, scale, groups: int, eps: float, *,
                         mesh=None):
    """Mamba-2's output norm: ``RMSNorm(y * silu(z))`` with the mean
    square taken over each of ``groups`` equal slices of the last
    dimension and one ``scale`` over all of it; float32 inside, ``y``'s
    dtype out. Recomputed in the backward: ``y`` and ``z`` are kept, in
    their own dtype, and none of the float32 products between. ``mesh``
    is the mesh the program is sharded over, if the caller knows one:
    ``norm_path`` decides from it between the kernels
    (``ops/pallas/gated_norm.py``) and the XLA function below."""
    if norm_path(y.shape, groups, mesh) == "pallas":
        return gated_norm.gated_norm(
            y, z, scale, groups=groups, eps=eps, mesh=mesh,
            batch_axes=program.batch_axes(mesh, y.shape[0]))
    return _gated_group_rms_norm_xla(y, z, scale, groups, eps)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _gated_group_rms_norm_xla(y, z, scale, groups: int, eps: float):
    dtype = y.dtype
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = g.shape
    g = g.reshape(*shape[:-1], groups, shape[-1] // groups)
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(shape) * scale.astype(jnp.float32)).astype(dtype)


def sigmoid_gated_head_rms_norm(o, gate, scale, heads: int, eps: float, *,
                                mesh=None, gate_fn: str = "sigmoid"):
    """Kimi Delta Attention's output gate: ``sigmoid(gate) *
    RMSNorm_head(o)``, the norm over each of ``heads`` equal slices of
    the last dimension with one ``scale`` [C / heads] shared by the
    heads. Not ``gated_group_rms_norm``: that one norms the *gated*
    product ``y * silu(z)``; this one gates the *normed* output, by a
    sigmoid. float32 inside, ``gate``'s dtype out; recomputed in the
    backward (``o`` and ``gate`` are kept, in their own dtype). ``mesh``
    is the mesh the program is sharded over, if the caller knows one:
    ``norm_path`` decides from it, a head a group, between the second
    kernel pair of ``ops/pallas/gated_norm.py`` (``head_gate_norm``)
    and the XLA function below. Notes ``kda_gate_path`` for the trace
    in progress. ``gate_fn`` ``"silu"`` is Gated DeltaNet's gate,
    ``silu(gate) * RMSNorm_head(o)``: the same two paths (a kernel pair
    of its own), the note ``gdn_gate_path``."""
    path = norm_path(o.shape, heads, mesh)
    tracing.note_trace(**{_HEAD_GATES[gate_fn][1]: path})
    if path == "pallas":
        return gated_norm.head_gate_norm(
            o, gate, scale, heads=heads, eps=eps, mesh=mesh,
            batch_axes=program.batch_axes(mesh, o.shape[0]),
            gate_fn=gate_fn)
    return _sigmoid_gated_head_rms_norm_xla(o, gate, scale, heads, eps,
                                            gate_fn)


# the output gate's function by name, and the note its path goes under
_HEAD_GATES = {"sigmoid": (jax.nn.sigmoid, "kda_gate_path"),
               "silu": (jax.nn.silu, "gdn_gate_path")}


@functools.partial(jax.checkpoint, static_argnums=(3, 4, 5))
def _sigmoid_gated_head_rms_norm_xla(o, gate, scale, heads: int, eps: float,
                                     gate_fn: str = "sigmoid"):
    """The output gate in XLA, under the gate's function by name (the
    sigmoid unless said: the name is from before there was a second)."""
    shape = o.shape
    x = o.astype(jnp.float32).reshape(*shape[:-1], heads, shape[-1] // heads)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    x = (x * scale.astype(jnp.float32)).reshape(shape)
    return (_HEAD_GATES[gate_fn][0](gate.astype(jnp.float32)) * x).astype(
        gate.dtype)

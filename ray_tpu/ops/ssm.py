"""State-space (Mamba) ops: the Mamba-2 selective scan in its chunked form,
the depthwise causal convolution (with its SiLU) in front of it and the
grouped gated RMSNorm behind it, and Kimi Delta Attention's output gate,
which stands where that norm does behind another recurrence. All four
are Pallas kernels where a TPU program can take them (below) and XLA
functions elsewhere, the convolution, the norm and the gate recomputed
in the backward on either path. The first three have one caller,
``models/nemotron_h.py::Mamba2Mixer``, which two models run: Nemotron-H's
``M`` layers (64 heads of 64, state 128 in 8 groups, chunk 128, 4 taps
with bias) and granite-4.0-h-micro's ``mamba`` layers
(``models/granite.py``: the same heads and state in **1 group**, chunk
**256**, every block recomputed under ``nn.remat``).

The recurrence, a head (``S`` is ``[P, N]``)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (x) B_t
    y_t = S_t . C_t + D * x_t

``mamba2_scan`` computes it a chunk of the sequence at a time (the
"state-space duality" form of Mamba-2, arXiv:2405.21060): inside a
chunk the quadratic term ``sum_{s<=t} exp(a_{s+1..t}) dt_s (C_t . B_s)
x_s`` as two matmuls round a ``[chunk, chunk]`` decay-masked score
square; each chunk's contribution to the state at its end as one
matmul; the states carried from chunk to chunk by a scan over the
chunks (64 steps at 8,192 tokens, each an elementwise update of
``[H, P, N]``); and the carried state's contribution to the chunk's
outputs as one more matmul. Matmul operands are in ``x``'s dtype
(bfloat16 in training) with float32 accumulation; ``dt``, the decays
and their cumulative sums are float32 throughout.

Written plainly, autodiff would keep every chunk's ``[H, chunk, chunk]``
decay and score squares for the backward: ~210 KB a token a layer in
float32. So the whole function is a ``jax.checkpoint`` whose policy
saves one thing, the states at the chunk boundaries (``[T / chunk, H,
P, N]`` float32: 134 MB a layer at 8,192 tokens): the backward
recomputes inside each chunk from the inputs and those states.

Two paths, chosen by ``scan_path()`` from the backend, the shapes and
the devices the program spans. On a TPU, where the shapes tile (a chunk
and a state of whole 128-lane tiles, each group's heads in blocks of
eight that fill whole tiles), the scan is two Pallas kernels, forward
and backward, under one ``custom_vjp`` (``ops/pallas/ssd_scan.py``,
``pallas_chunked``): a chunk's squares live and die in VMEM, the state
is carried across the chunks in scratch, and the boundary states are
the one thing kept. A program on one device calls them bare; one whose
mesh shards the batch and nothing else (``dp``, ``fsdp``) calls them
under a ``shard_map`` over the batch, each device its own sequences.
Everywhere else it is pure XLA (``_ssd`` below, ``chunked_xla``): the
chunk terms are batched matmuls that XLA places on the MXU, the
squares arrays in HBM. Same mathematics, same precisions, same
residual. A sequence split over chips (``sp``) would need the state
passed between chips; the model refuses it by name. The kernels'
forward rule names its two results, the output ``y`` and the states
entering the chunks (``SCAN_OUT``, ``SCAN_STATES``, 67 MB each a layer
at 8,192 rows of 64 x 64 and chunk 256): a recomputed block whose
policy lists them runs the forward kernel once a layer, not twice, and
outside a policy the names are the identity. One group's ``C.B^T``
square is made once a head block of eight heads: once a group at
Nemotron's 8 heads a group, eight times at Granite's 64
(``score_squares_per_group``, the note ``ssm_blocks_per_group``).

The gated norm follows the scan: ``norm_path()`` gives it its own two
kernels (``ops/pallas/gated_norm.py``, ``pallas``) on a TPU where each
of its groups is whole 128-lane tiles and the program is one the scan's
kernels serve, bare or under the same ``shard_map`` over the batch, so
that the norm stays in the row-major ``[B, T, H*P]`` the scan's kernel
writes and ``out_proj``'s matmul reads; behind a custom call the XLA
function's reshape to ``[.., groups, C / groups]`` was a relayout of a
float32 array three times a layer. Everywhere else that XLA function
runs (``xla``).

The convolution goes in front of the scan: ``conv_path()`` gives it
its own two kernels (``ops/pallas/causal_conv.py``, ``pallas``) on a
TPU where ``x`` is ``[b, T, C]`` with ``C`` whole 128-lane tiles, a row
reads no more rows before itself than one sublane tile holds, and the
program is one device's or shards the batch alone (the same
``_kernel_batch_axes``): each pass reads its operands once, the rows
before a block through a second view of ``x`` and the rows after it
carried in VMEM, float32 inside from the operands' own dtype. As XLA's
fusions over a padded copy the forward ran at 2.9 times its bytes and
the backward at 5.7 (PERF.md section 6, PRs 47 and 55). Everywhere else
(the CPU, the tiny presets' widths, ``sp`` / ``tp``) the XLA function
runs (``xla``), in the compute type, and is what the kernels are tested
against. Both keep ``x`` for the backward and nothing else. Every call
notes ``conv_path``, ``conv_taps`` and ``conv_cols`` for the trace in
progress.

Kimi Delta Attention (``ops/kda.py``, ``models/kimi_linear.py``) takes
two things from here: the convolution, three times a layer and without
a bias (36 kernel calls a step over four layers), and
``sigmoid_gated_head_rms_norm``, its own output gate (a sigmoid on the
*normed* output, where Mamba-2 norms the gated one; ``scale`` one
head's width, shared by the heads). It decides as the norm does, by
the same ``norm_path()`` with a head a group: ``pallas`` is the second
kernel pair of ``ops/pallas/gated_norm.py`` (``head_gate_norm``; the
same grid, blocks and kernel shells, another strip's arithmetic), which
reads the recurrence's float32 ``o`` and the bfloat16 ``gate`` once a
pass in the row-major ``[B, T, H*K]`` that the recurrence's kernel
writes and ``W_o``'s matmul reads (8 kernel calls a step over four
layers: a recomputed block keeps the gated output, so the forward
kernel runs in the step's forward pass alone); ``xla`` the reshape-and-
mean function under a ``jax.checkpoint``, the CPU's path and what the
tests hold the kernels to. As that function it was 41.2 ms of the
Kimi-Linear cell's step in passes over ``[1, 16384, 4096]`` float32
arrays (PERF.md section 6, PR 58). Every call notes ``kda_gate_path``.

**Mamba-1** (``mamba1_scan``, Phi-4-mini-flash's ``M`` layers,
``models/phi4flash.py``) is the older recurrence: the decay differs by
channel *and* by state, ``exp(dt_t[c] * A[c, n])`` over a ``[C, N]``
state, with one ``B_t``, ``C_t`` for all channels. No matmul form
covers it (the duality above needs one scalar decay a head), so
neither ``_ssd`` nor the kernels of ``ops/pallas/ssd_scan.py`` can run
it: float32 elementwise work throughout, in a kernel pair that keeps
the state in VMEM (``pallas_chunked``, ``ops/pallas/mamba1_scan.py``)
or an associative scan a chunk under a ``lax.scan`` (``xla_chunked``),
as ``mamba1_path`` decides. It takes the convolution with its bias.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.pallas import causal_conv, gated_norm, ssd_scan
from ray_tpu.util import tracing

_BOUNDARY = "ssm_boundary_states"
# what a recomputed block keeps of the scan's kernels (``ssd_scan.py``)
SCAN_OUT, SCAN_STATES = ssd_scan.SCAN_OUT, ssd_scan.SCAN_STATES
# and of the mixer's ``in_proj``: the three parts its product is split
# into (``z``, ``xBC``, ``dt``), which ``Mamba2Mixer`` names
IN_PROJ_PARTS = ("mamba_z", "mamba_xbc", "mamba_dt")
# what ``parallel/sharding.py`` maps the logical "batch" to
_BATCH_AXES = ("dp", "fsdp")


def scan_path(x_shape, state_shape, chunk: int, mesh=None) -> str:
    """Which scan ``mamba2_scan`` compiles for ``x`` [b, T, H, P] and
    ``B`` / ``C`` [b, T, G, N] at this chunk: ``pallas_chunked`` on a
    TPU where the kernels tile the shapes and ``_kernel_batch_axes``
    finds the program one the kernels can serve, else ``chunked_xla``."""
    (h, p), (g, n) = x_shape[-2:], state_shape[-2:]
    if (jax.default_backend() == "tpu"
            and ssd_scan.shapes_ok(h, p, g, n, chunk)
            and _kernel_batch_axes(mesh, x_shape[0]) is not None):
        return "pallas_chunked"
    return "chunked_xla"


def score_squares_per_group(h: int, g: int, path: str) -> int:
    """How many times a chunk's ``C.B^T`` square is made for one group
    of ``h // g`` heads on ``path``: once a head block of the kernels'
    grid, once in ``_ssd``'s einsum."""
    return ssd_scan.blocks_per_group(h, g) if path == "pallas_chunked" else 1


def norm_path(shape, groups: int, mesh=None) -> str:
    """Which gated norm ``gated_group_rms_norm`` compiles for ``y``
    [b, T, C] in ``groups`` groups, and ``sigmoid_gated_head_rms_norm``
    for ``o`` [b, T, C] in as many heads: ``pallas`` exactly where the
    scan takes its kernels (a TPU, each group whole 128-lane tiles, and
    ``_kernel_batch_axes`` finds the program one the kernels can
    serve), else ``xla``."""
    if (jax.default_backend() == "tpu" and len(shape) == 3
            and gated_norm.shapes_ok(shape[-1], groups)
            and _kernel_batch_axes(mesh, shape[0]) is not None):
        return "pallas"
    return "xla"


def conv_path(shape, taps: int, mesh=None) -> str:
    """Which convolution ``causal_conv1d_silu`` compiles for ``x``
    [b, T, C] at ``taps`` taps: ``pallas`` (the kernels of
    ``ops/pallas/causal_conv.py``) on a TPU where ``C`` is whole
    128-lane tiles, the rows a row reads before itself fit one sublane
    tile and ``_kernel_batch_axes`` finds the program one the kernels
    can serve, else ``xla``."""
    if (jax.default_backend() == "tpu" and len(shape) == 3
            and causal_conv.shapes_ok(shape[-1], taps)
            and _kernel_batch_axes(mesh, shape[0]) is not None):
        return "pallas"
    return "xla"


def _kernel_batch_axes(mesh, batch: int):
    """The mesh axes to ``shard_map`` the kernels over, ``()`` for a
    one-device program, ``None`` where the kernels cannot run: a
    ``pallas_call`` has no SPMD partitioning rule, so a program that
    spans devices reaches it only through a ``shard_map``. The scan is
    independent a sequence, so the batch's axes are the ones it can be
    mapped over; a mesh with any other real axis, or a batch its
    devices do not divide (the tiny one of init tracing), takes the XLA
    path. Without a mesh nothing says how many devices the program
    spans and the process's device count stands in for it, as in
    ``ops/attention.py::causal_attention``."""
    if mesh is None:
        return () if jax.device_count() == 1 else None
    real = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
    if set(real) <= set(_BATCH_AXES) and batch % mesh.size == 0:
        return real
    return None


def _ssd(x, dt, A, B, C, chunk: int):
    """The chunked scan without the ``D`` skip, ``T`` a multiple of
    ``chunk``. x [b, T, H, P]; dt [b, T, H] float32; A [H] float32;
    B, C [b, T, G, N]; returns float32 [b, T, H, P]."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    R, nc, L = H // G, T // chunk, chunk
    dtype = x.dtype
    xc = x.reshape(b, nc, L, G, R, P)
    dtc = dt.reshape(b, nc, L, G, R)
    Bc = B.reshape(b, nc, L, G, N)
    Cc = C.reshape(b, nc, L, G, N)
    # log-decay a step, and its running sum inside the chunk (<= 0)
    cum = jnp.cumsum(dtc * A.reshape(G, R), axis=2)     # [b, nc, L, G, R]

    # inside a chunk: t reads s <= t through exp(cum_t - cum_s)
    scores = jnp.einsum("bctgn,bcsgn->bcgts", Cc, Bc,
                        preferred_element_type=jnp.float32)
    ct = jnp.moveaxis(cum, 2, -1)                       # [b, nc, G, R, L]
    reach = ct[..., :, None] - ct[..., None, :]         # [.., t, s]
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(causal, reach, -jnp.inf))
    weight = (scores[:, :, :, None] * decay
              * jnp.moveaxis(dtc, 2, -1)[..., None, :])  # [b,nc,G,R,t,s]
    y = jnp.einsum("bcgrts,bcsgrp->bctgrp", weight.astype(dtype), xc,
                   preferred_element_type=jnp.float32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dtc        # [b, nc, L, G, R]
    own = jnp.einsum("bcsgrp,bcsgn->bcgrpn",
                     (xc * to_end[..., None]).astype(dtype), Bc,
                     preferred_element_type=jnp.float32)
    keep = jnp.exp(cum[:, :, -1])                       # [b, nc, G, R]

    # carry: the state entering chunk c (zeros for the first)
    def step(state, inp):
        own_c, keep_c = inp
        return keep_c[..., None, None] * state + own_c, state

    _, entering = lax.scan(
        step, jnp.zeros_like(own[:, 0]),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(keep, 1, 0)))
    entering = checkpoint_name(jnp.moveaxis(entering, 0, 1), _BOUNDARY)

    # what the carried state adds inside the chunk
    y = y + jnp.einsum("bctgn,bcgrpn->bctgrp", Cc, entering.astype(dtype),
                       preferred_element_type=jnp.float32
                       ) * jnp.exp(cum)[..., None]
    return y.reshape(b, T, H, P)


def mamba2_scan(x, dt, A, B, C, D, *, chunk: int = 128, mesh=None):
    """The Mamba-2 selective scan, chunked; backward by recomputation
    from the chunk-boundary states.

    x:  [batch, T, H, P]  the heads' inputs (compute dtype)
    dt: [batch, T, H]     step sizes after softplus, float32
    A:  [H]               negative decay rates, float32
    B, C: [batch, T, G, N]  input and output projections of the state;
                          head ``h`` uses group ``h // (H // G)``
    D:  [H]               the skip's weight
    Returns ``y`` [batch, T, H, P] in ``x``'s dtype. ``T`` need not be
    a multiple of ``chunk``: the tail is padded with steps that neither
    decay nor write the state. ``mesh`` is the mesh the program is
    sharded over, if the caller knows one: ``scan_path`` decides from
    it.
    """
    if scan_path(x.shape, B.shape, chunk, mesh) == "pallas_chunked":
        return ssd_scan.ssd_scan(
            x, dt, A, B, C, D, chunk=chunk, mesh=mesh,
            batch_axes=_kernel_batch_axes(mesh, x.shape[0]))
    return jax.checkpoint(
        functools.partial(_padded_scan, chunk=chunk),
        policy=jax.checkpoint_policies.save_only_these_names(_BOUNDARY))(
            x, dt, A, B, C, D)


def _padded_scan(x, dt, A, B, C, D, *, chunk: int):
    T = x.shape[1]
    pad = (-T) % chunk
    xp, dtp, Bp, Cp = ((jnp.pad(z, ((0, 0), (0, pad))
                                + ((0, 0),) * (z.ndim - 2)) if pad else z)
                       for z in (x, dt, B, C))
    y = _ssd(xp, dtp.astype(jnp.float32), A.astype(jnp.float32), Bp, Cp,
             chunk)[:, :T]
    return (y + D.astype(jnp.float32)[:, None] * x).astype(x.dtype)


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
            batch_axes=_kernel_batch_axes(mesh, x.shape[0]))
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
            batch_axes=_kernel_batch_axes(mesh, y.shape[0]))
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
            batch_axes=_kernel_batch_axes(mesh, o.shape[0]),
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


def mamba1_path(shape, states: int, chunk: int, mesh=None) -> str:
    """Which scan ``mamba1_scan`` compiles for ``x`` [b, T, C] with
    ``states`` states a channel on this mesh: ``pallas_chunked`` (the
    kernels of ``ops/pallas/mamba1_scan.py``) on a TPU where they tile
    the shapes and the program is one device's
    (``_kernel_batch_axes(..) == ()``: no cell runs this scan on a mesh,
    so ``ssd_scan``'s ``shard_map`` over the batch's axes is not carried
    over), else ``xla_chunked`` at this ``chunk``, which is that path's
    parameter alone. Raises where the program spans chips in a way that
    would split a sequence or its channels."""
    from ray_tpu.ops.pallas import mamba1_scan as kernels
    from ray_tpu.parallel.mesh import AXIS_SP, AXIS_TP
    if mesh is not None:
        for axis, what in ((AXIS_SP, "the sequence split over chips (a "
                            "recurrent state passed from chip to chip)"),
                           (AXIS_TP, "the channels split over chips")):
            if mesh.shape.get(axis, 1) > 1:
                raise NotImplementedError(
                    f"mamba1 on a mesh with {axis}={mesh.shape[axis]}: "
                    f"{what} is not implemented for it; dp and fsdp shard "
                    "the batch and need nothing")
    if chunk < 1:
        raise ValueError(f"a chunk of {chunk} rows")
    if (jax.default_backend() == "tpu"
            and kernels.shapes_ok(shape[-1], states)
            and _kernel_batch_axes(mesh, shape[0]) == ()):
        return "pallas_chunked"
    return "xla_chunked"


def _mamba1_decay(dt, A):
    """``exp(dt_t (x) A)`` a row: dt [b, L, C], A [N, C] -> [b, L, N, C].
    The channels are the minor dimension throughout (``[.., N, C]``: 16
    sublanes by whole 128-lane tiles, where ``[.., C, N]`` would be
    seven parts padding)."""
    return jnp.exp(dt[:, :, None, :] * A)


def _mamba1_write(dt, x, B):
    """``(dt_t * x_t) (x) B_t``, what a row writes into the state:
    [b, L, N, C]."""
    return (dt * x)[:, :, None, :] * B[..., None]


def _mamba1_chunk(state, rows, A):
    """One chunk of ``L`` rows: (the state it leaves, its outputs
    [b, L, C]). state [b, N, C]; rows: x, dt [b, L, C], B, C [b, L, N];
    A [N, C]; all float32."""
    x, dt, B, C = rows

    def then(first, second):
        (a1, b1), (a2, b2) = first, second
        return a1 * a2, a2 * b1 + b2

    # row t: (the product of the decays from the chunk's first row to t,
    # what the chunk's own rows have written into the state by t)
    decay_to, own = lax.associative_scan(
        then, (_mamba1_decay(dt, A), _mamba1_write(dt, x, B)), axis=1)
    states = decay_to * state[:, None] + own
    return states[:, -1], jnp.sum(states * C[..., None], axis=2)


def _mamba1_walk(one, state, rows):
    """The chunks in order, each handed the state the one before left:
    ``one(state, a chunk's rows) -> (state, y)``."""
    return lax.scan(one, state, rows)


def mamba1_scan(x, dt, A, B, C, D, *, chunk: int = 4, mesh=None):
    """The Mamba-1 selective scan; backward by recomputation of each
    chunk (or row block) from the state that entered it.

    x:  [batch, T, C]   the channels' inputs (any dtype)
    dt: [batch, T, C]   step sizes after softplus, float32
    A:  [C, N]          negative decay rates, a channel and a state
    B, C: [batch, T, N] input and output projections of the state, one
                        for all channels
    D:  [C]             the skip's weight
    Returns ``y`` [batch, T, C] float32::

        h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t    [C, N]
        y_t = h_t . C_t + D * x_t

    ``mesh`` is the mesh the program is sharded over, if the caller
    knows one: ``mamba1_path`` decides (and refuses) from it, and from
    the backend and the shapes, between two ways of doing the same
    float32 arithmetic. ``pallas_chunked``: the kernel pair of
    ``ops/pallas/mamba1_scan.py``, the state in VMEM along a sequence's
    row blocks (of its own ``ROWS``; ``chunk`` is not its parameter).
    ``xla_chunked``, everywhere else and the reference the kernels are
    tested against: inside a chunk an associative scan over the rows'
    (decay, write) pairs; the ``[N, C]`` state carried from chunk to
    chunk by a ``lax.scan``; each chunk a ``jax.checkpoint``, so that the
    trajectory ``[T, C, N]`` (1.34 GB a layer at 4,096 rows of 5,120
    channels) is never whole in HBM: the backward keeps the inputs and
    the state entering each chunk. **Short chunks win on the chip** for
    it: a layer at 4,096 rows of 5,120 channels, forward + backward,
    reads 18.7 ms at chunks of 2 rows, 21.6 at 4, 26.7 at 8, 28.3 at 16,
    93.1 at 64 and 219-303 at 128-512 (PERF.md section 6, PR 48; the
    kernels: 5.7, PR 49): a chunk's ``[L, N, C]`` arrays pass through HBM
    some thirty times in the associative scan's levels, a turn of the
    loop costs ~2.6 us, and the states kept (``[T / L, N, C]`` float32:
    335 MB a layer at 4) grow as the chunk shrinks. ``T`` need not be
    whole chunks on either path: the tail is padded with rows that
    neither decay nor write the state."""
    path = mamba1_path(x.shape, A.shape[1], chunk, mesh)
    if path == "pallas_chunked":
        from ray_tpu.ops.pallas import mamba1_scan as kernels
        tracing.note_trace(ssm_path=path, ssm_chunk=kernels.ROWS)
        return kernels.mamba1_scan(x, dt, A, B, C, D)
    tracing.note_trace(ssm_path=path, ssm_chunk=chunk)
    return _mamba1_xla_chunked(x, dt, A, B, C, D, chunk)


def _mamba1_xla_chunked(x, dt, A, B, C, D, chunk: int):
    """``mamba1_scan`` on its XLA path, whatever the backend."""
    b, t, c = x.shape
    f32 = jnp.float32
    x, dt, B, C = (z.astype(f32) for z in (x, dt, B, C))
    pad = (-t) % chunk
    # [chunks, b, L, .]: no row moves for a batch of one
    rows = tuple(jnp.moveaxis(
        jnp.pad(z, ((0, 0), (0, pad), (0, 0))).reshape(
            b, (t + pad) // chunk, chunk, z.shape[-1]), 1, 0)
        for z in (x, dt, B, C))
    A_t = A.astype(f32).T
    one = jax.checkpoint(lambda state, r: _mamba1_chunk(state, r, A_t))
    _, y = _mamba1_walk(one, jnp.zeros((b, A_t.shape[0], c), f32), rows)
    y = jnp.moveaxis(y, 0, 1).reshape(b, t + pad, c)[:, :t]
    return y + D.astype(f32) * x

"""The Mamba-2 selective scan in its chunked form, and nothing else
(the convolution in front of it is ``ops/conv1d.py``'s, the gated norm
behind it ``ops/gated_norm.py``'s, Mamba-1 ``ops/mamba1.py``'s, which
program a kernel may run in ``ops/pallas/program.py``'s). One caller,
``models/nemotron_h.py::Mamba2Mixer``, which two models run: Nemotron-H's
``M`` layers (64 heads of 64, state 128 in 8 groups, chunk 128) and
granite-4.0-h-micro's ``mamba`` layers (``models/granite.py``: the same
heads and state in **1 group**, chunk **256**, every block recomputed
under ``nn.remat``).

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
entering the chunks (``ops/remat.py::SSD_SCAN_OUT``,
``SSD_SCAN_STATES``, 67 MB each a layer at 8,192 rows of 64 x 64 and
chunk 256): a recomputed block whose
policy lists them runs the forward kernel once a layer, not twice, and
outside a policy the names are the identity. One group's ``C.B^T``
square is made once a head block of eight heads: once a group at
Nemotron's 8 heads a group, eight times at Granite's 64
(``score_squares_per_group``, the note ``ssm_blocks_per_group``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.pallas import program, ssd_scan

_BOUNDARY = "ssm_boundary_states"


def scan_path(x_shape, state_shape, chunk: int, mesh=None) -> str:
    """Which scan ``mamba2_scan`` compiles for ``x`` [b, T, H, P] and
    ``B`` / ``C`` [b, T, G, N] at this chunk: ``pallas_chunked`` on a
    TPU where the kernels tile the shapes and ``program.batch_axes``
    finds the program one the kernels can serve, else ``chunked_xla``."""
    (h, p), (g, n) = x_shape[-2:], state_shape[-2:]
    if (jax.default_backend() == "tpu"
            and ssd_scan.shapes_ok(h, p, g, n, chunk)
            and program.batch_axes(mesh, x_shape[0]) is not None):
        return "pallas_chunked"
    return "chunked_xla"


def score_squares_per_group(h: int, g: int, path: str) -> int:
    """How many times a chunk's ``C.B^T`` square is made for one group
    of ``h // g`` heads on ``path``: once a head block of the kernels'
    grid, once in ``_ssd``'s einsum."""
    return ssd_scan.blocks_per_group(h, g) if path == "pallas_chunked" else 1


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
            batch_axes=program.batch_axes(mesh, x.shape[0]))
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

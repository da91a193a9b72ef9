"""The Mamba-1 selective scan (Phi-4-mini-flash's ``M`` layers,
``models/phi4flash.py``): the older recurrence, whose decay differs by
channel *and* by state, ``exp(dt_t[c] * A[c, n])`` over a ``[C, N]``
state, with one ``B_t``, ``C_t`` for all channels. No matmul form covers
it (Mamba-2's duality, ``ops/ssm.py``, needs one scalar decay a head),
so neither ``ssm._ssd`` nor the kernels of ``ops/pallas/ssd_scan.py``
can run it: float32 elementwise work throughout, in a kernel pair that
keeps the state in VMEM (``pallas_chunked``,
``ops/pallas/mamba1_scan.py``) or an associative scan a chunk under a
``lax.scan`` (``xla_chunked``), as ``mamba1_path`` decides. It takes the
convolution (``ops/conv1d.py``) with its bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.pallas import program
from ray_tpu.util import tracing


def mamba1_path(shape, states: int, chunk: int, mesh=None) -> str:
    """Which scan ``mamba1_scan`` compiles for ``x`` [b, T, C] with
    ``states`` states a channel on this mesh: ``pallas_chunked`` (the
    kernels of ``ops/pallas/mamba1_scan.py``) on a TPU where they tile
    the shapes and the program is one device's
    (``program.batch_axes(..) == ()``: no cell runs this scan on a mesh,
    so ``ssd_scan``'s ``shard_map`` over the batch's axes is not carried
    over), else ``xla_chunked`` at this ``chunk``, which is that path's
    parameter alone. Raises where the program spans chips in a way that
    would split a sequence or its channels."""
    from ray_tpu.ops.pallas import mamba1_scan as kernels
    program.refuse(
        mesh, "mamba1",
        sp="the sequence split over chips (a recurrent state passed from "
           "chip to chip)",
        tp="the channels split over chips")
    if chunk < 1:
        raise ValueError(f"a chunk of {chunk} rows")
    if (jax.default_backend() == "tpu"
            and kernels.shapes_ok(shape[-1], states)
            and program.batch_axes(mesh, shape[0]) == ()):
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

"""Which program a bare kernel may run in.

A ``pallas_call`` has no SPMD partitioning rule: a program that spans
devices reaches a kernel only through a ``shard_map`` ("Mosaic kernels
cannot be automatically partitioned" otherwise, at compile, which a test
on one device never sees). Every dispatcher of ``ray_tpu/ops`` asks the
same three things of the mesh it was given, answered here once: **the
rule** (``batch_axes`` for a kernel that is independent a sequence,
``token_axes`` for a layer that is independent a token), **the map**
(``over_batch``) and **the refusal** (``refuse``). A ``*_path`` function
is then three observations: the backend, its kernel's own ``shapes_ok``,
and ``batch_axes``. The module sits with the kernels so that imports point
one way (models -> ``ops/*.py`` -> ``ops/pallas/*.py``); it imports
``jax`` and the mesh axes' names and builds nothing at import.
"""

from __future__ import annotations

import math

import jax

from ray_tpu.parallel.mesh import AXIS_DP, AXIS_FSDP, AXIS_SP

# what ``parallel/sharding.py`` maps the logical "batch" to
BATCH_AXES = (AXIS_DP, AXIS_FSDP)


def batch_axes(mesh, batch: int):
    """The mesh axes to ``shard_map`` the kernels over, ``()`` for a
    one-device program, ``None`` where the kernels cannot run. A kernel
    that is independent a sequence can be mapped over the batch's axes;
    a mesh with any other real axis, or a batch its devices do not
    divide (the tiny one of init tracing), takes the XLA path. Without
    a mesh nothing says how many devices the program spans and the
    process's device count stands in for it, as in
    ``ops/attention.py::causal_attention``."""
    if mesh is None:
        return () if jax.device_count() == 1 else None
    real = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
    if set(real) <= set(BATCH_AXES) and batch % mesh.size == 0:
        return real
    return None


def token_axes(mesh, batch: int, seq: int):
    """(batch axes, sequence axis or None): the mesh axes of size > 1
    that shard a ``[batch, seq, ...]`` activation's tokens, as
    ``train.step.batch_spec`` places them: dp and fsdp on the batch, sp
    on the sequence. ``((), None)`` where a layer that is independent a
    token stays one global program: no mesh, one device, no such axis,
    or shapes the axes do not divide. What else a caller has against a
    mesh (a head sharded on its vocabulary, experts sharded over
    ``ep``) it says before it asks."""
    if mesh is None or mesh.size == 1:
        return (), None
    rows = tuple(a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1)
    seq_axis = AXIS_SP if mesh.shape.get(AXIS_SP, 1) > 1 else None
    if (batch % math.prod(mesh.shape[a] for a in rows)
            or (seq_axis and seq % mesh.shape[seq_axis])):
        return (), None
    return rows, seq_axis


def over_batch(fn, mesh, axes, in_specs, out_specs):
    """``fn`` on each device's rows of the batch: ``fn`` itself where
    ``axes`` is empty (a one-device program calls the kernels bare),
    else under a ``shard_map`` over ``axes`` of ``mesh``. A spec is the
    dimension of the operand or result that carries the batch, or
    ``None`` for one held whole on every device (weights: the map's
    transpose sums their cotangents over the axes once); ``out_specs``
    is one spec or a tuple of them. All mesh axes are manual
    (``check_vma=False``, no ``axis_names``): with only some manual a
    bf16 sum's reduction aborts XLA's CPU compiler."""
    if not axes:
        return fn
    from jax.sharding import PartitionSpec

    def spec(dim):
        if dim is None:
            return PartitionSpec()
        return PartitionSpec(*(None,) * dim, tuple(axes))

    outs = (tuple(map(spec, out_specs)) if isinstance(out_specs, tuple)
            else spec(out_specs))
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(map(spec, in_specs)),
                         out_specs=outs, check_vma=False)


def refuse(mesh, who: str, **whats: str) -> None:
    """Raises ``NotImplementedError`` where ``mesh`` has a real axis
    among ``whats`` (``{axis: what it would take}``), naming ``who``
    met it, the axis, its size and what is missing; nothing on no
    mesh."""
    if mesh is None:
        return
    for axis, what in whats.items():
        if mesh.shape.get(axis, 1) > 1:
            raise NotImplementedError(
                f"{who} on a mesh with {axis}={mesh.shape[axis]}: {what} "
                "is not implemented for it; dp and fsdp shard the batch "
                "and need nothing")

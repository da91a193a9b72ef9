"""Device mesh construction.

The mesh is the framework's unit of accelerator scheduling: an
ICI-connected TPU slice maps to one ``jax.sharding.Mesh``, and the
scheduler gang-schedules whole meshes (SURVEY.md §7.1 step 5). This
module only builds meshes; placement is the scheduler's job.

Design note vs the reference: Ray models a TPU slice as a custom
resource ("TPU-v5litepod-8-head", tpu.py:381) and leaves device
topology to the user's framework. Here topology is first-class: a
MeshSpec names logical axes with sizes, and axis ORDER maps
minor-to-major onto the physical ICI topology so that the
most-communication-hungry axis (tp) lands on the fastest rings.
"""

from __future__ import annotations

from dataclasses import dataclass, field

AXIS_DP = "dp"
AXIS_FSDP = "fsdp"
AXIS_TP = "tp"
AXIS_SP = "sp"
AXIS_EP = "ep"
AXIS_PP = "pp"

# Canonical order, outermost (slowest / DCN-friendly) to innermost
# (fastest ICI): pipeline and data cross slices fine; tensor wants the
# tightest rings.
CANONICAL_ORDER = (AXIS_PP, AXIS_DP, AXIS_FSDP, AXIS_EP, AXIS_SP, AXIS_TP)


@dataclass
class MeshSpec:
    """Named parallelism axes, e.g. ``MeshSpec(dp=2, tp=4)``.

    One axis may be -1, meaning "all remaining devices". Axes of size 1
    are kept in the mesh (so PartitionSpecs referencing them are always
    valid) unless ``squeeze=True``.
    """

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1
    squeeze: bool = False

    def axes(self) -> dict[str, int]:
        return {AXIS_PP: self.pp, AXIS_DP: self.dp, AXIS_FSDP: self.fsdp,
                AXIS_EP: self.ep, AXIS_SP: self.sp, AXIS_TP: self.tp}

    def resolve(self, n_devices: int) -> dict[str, int]:
        axes = self.axes()
        unknown = [k for k, v in axes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError("at most one axis may be -1")
        known = 1
        for k, v in axes.items():
            if v != -1:
                if v <= 0:
                    raise ValueError(f"axis {k} must be positive or -1")
                known *= v
        if unknown:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {known}")
            axes[unknown[0]] = n_devices // known
        else:
            total = known
            if total > n_devices:
                raise ValueError(
                    f"mesh axes {axes} need {total} devices, have "
                    f"{n_devices}")
            # total < n_devices is allowed: the mesh uses the first
            # `total` devices (handled by make_mesh).
        if self.squeeze:
            axes = {k: v for k, v in axes.items() if v > 1} or {AXIS_DP: 1}
        return axes


def make_mesh(spec: MeshSpec | dict[str, int] | None = None,
              devices=None):
    """Build a Mesh over ``devices`` (default: all local devices).

    Uses ``jax.make_mesh`` so XLA chooses a device order matching the
    physical ICI topology for the requested logical shape.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if spec is None:
        spec = MeshSpec(dp=-1)
    if isinstance(spec, dict):
        ms = MeshSpec()
        for k, v in spec.items():
            if not hasattr(ms, k):
                raise ValueError(f"unknown mesh axis {k!r}")
            setattr(ms, k, v)
        spec = ms
    axes = spec.resolve(n)
    names = tuple(axes.keys())
    shape = tuple(axes.values())
    import math
    total = math.prod(shape)
    if total < n:
        devices = devices[:total]
    # Auto axis types: we use classic pjit sharding propagation with
    # with_sharding_constraint (jax 0.9 defaults make_mesh to Explicit).
    auto = (jax.sharding.AxisType.Auto,) * len(names)
    return jax.make_mesh(shape, names, devices=devices,
                         axis_types=auto)


def local_mesh(**axes) -> "jax.sharding.Mesh":  # noqa: F821
    """Convenience: ``local_mesh(dp=2, tp=4)`` over local devices."""
    return make_mesh(axes or None)


def mesh_size(mesh) -> int:
    import math
    return math.prod(mesh.shape.values())

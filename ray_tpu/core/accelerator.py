"""TPU accelerator detection.

Analog of the reference's ``TPUAcceleratorManager``
(``python/ray/_private/accelerators/tpu.py:71``): detect chips from
``/dev/accel*`` / ``/dev/vfio/*`` device files, with env-var override,
without importing jax (importing jax grabs the TPU runtime, which must
only happen in the process that will own the chips).
"""

from __future__ import annotations

import glob
import os


def detect_tpu_chips_with_source() -> tuple[int, str]:
    """(chip count, what gave it): the ``RAY_TPU_CHIPS`` override, or
    the first device-file glob that matches. On a v5e host each chip
    is one numbered VFIO group (``/dev/vfio/<n>``, next to the
    ``/dev/vfio/vfio`` control node); older hosts expose
    ``/dev/accel<n>``."""
    override = os.environ.get("RAY_TPU_CHIPS")
    if override:
        try:
            return int(override), "RAY_TPU_CHIPS"
        except ValueError:
            raise ValueError(
                f"RAY_TPU_CHIPS={override!r} is not an integer") from None
    for pattern in ("/dev/accel*", "/dev/vfio/[0-9]*"):
        found = glob.glob(pattern)
        if found:
            return len(found), pattern
    return 0, "none"


def detect_tpu_chips() -> int:
    return detect_tpu_chips_with_source()[0]


def tpu_pod_type() -> str | None:
    """GCE metadata accelerator-type (e.g. v5litepod-8); None off-GCE."""
    return os.environ.get("TPU_ACCELERATOR_TYPE")


def tpu_worker_id() -> int:
    return int(os.environ.get("TPU_WORKER_ID", "0"))


def tpu_gang_resources() -> dict[str, float]:
    """Pod-slice gang-scheduling resources (reference:
    TPU-{pod_type}-head at tpu.py:381-386): worker 0 of a slice
    carries ``TPU-<type>-head: 1`` so a gang placement targets whole
    slices atomically."""
    out: dict[str, float] = {}
    pod = tpu_pod_type()
    if pod and tpu_worker_id() == 0:
        out[f"TPU-{pod}-head"] = 1.0
    return out


def get_tpu_ids() -> list[int]:
    """Chip indices visible to THIS process (reference analog:
    ray.get_gpu_ids for the accelerator the scheduler manages).
    Inside a CPU-only worker (the runtime sets ``JAX_PLATFORMS=cpu``
    for every worker that holds no TPU resource) this is []. A
    TPU-holding worker or the driver sees every chip of its host: the
    runtime does NOT pin chips per worker, so ``num_tpus`` is
    bookkeeping for the scheduler and two TPU workers on one host
    would both claim every chip. The shapes that work (and that
    ``chip_smoke.py`` runs) are one TPU worker per host, holding one
    chip of a one-chip host or all chips of a multi-chip host.
    ``TPU_VISIBLE_CHIPS``, when an external launcher exported it,
    narrows the list."""
    vis = os.environ.get("TPU_VISIBLE_CHIPS")
    if vis:
        return [int(x) for x in vis.split(",") if x.strip() != ""]
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        return []
    return list(range(detect_tpu_chips()))


def get_gpu_ids() -> list[int]:
    """Compatibility shim for code written against the reference's
    ray.get_gpu_ids(): this framework schedules TPUs, not GPUs, so
    the assigned-GPU list comes straight from CUDA_VISIBLE_DEVICES
    (set by an external launcher if at all) and is [] on TPU hosts."""
    vis = os.environ.get("CUDA_VISIBLE_DEVICES", "").strip()
    if not vis or vis == "NoDevFiles":
        return []
    out = []
    for x in vis.split(","):
        x = x.strip()
        if x.isdigit():
            out.append(int(x))
    return out

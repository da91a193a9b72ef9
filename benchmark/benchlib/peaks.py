"""Published peak rates of one chip, keyed by jax's ``device_kind``.

Copied from ``ray_tpu/util/device_peaks.py`` (the program may change
its copy; the yardstick keeps this one) and extended with memory
bandwidth for the kernel roofline. A device that is not in the table is
an error, never a default.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (System architecture): 197
# TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip, 16 GB of HBM.
PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise LookupError(
            f"no published {what} for device_kind {device_kind!r}; add "
            f"it to benchmark/benchlib/peaks.py with its source "
            f"(known: {sorted(PEAKS)})") from None

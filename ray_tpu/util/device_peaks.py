"""Published peak rates of one chip, keyed by jax's ``device_kind``.

The denominator of every utilization the benchmarks print. A device
that is not in the table is an error, never a default: a utilization
against another chip's peak is a wrong number with a right name.
"""

from __future__ import annotations

# device_kind -> peaks, each row with its source.
PEAKS: dict[str, dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip.
    "TPU v5 lite": {"bf16_flops": 197e12},
}


def peak_bf16_flops(device_kind: str) -> float:
    try:
        return PEAKS[device_kind]["bf16_flops"]
    except KeyError:
        raise LookupError(
            f"no published peak for device_kind {device_kind!r}; add "
            f"it to ray_tpu.util.device_peaks.PEAKS with its source "
            f"(known: {sorted(PEAKS)})") from None

"""Published peaks of each accelerator the benchmark measures on, keyed
by JAX's ``device_kind``.  A device that is not listed is an error: a
share of an assumed peak would be a number about nothing."""

from __future__ import annotations

import dataclasses

__all__ = ["Peak", "PEAKS", "peak_for"]


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float  # FLOP/s of the matrix units in bfloat16
    hbm_bytes_s: float  # bytes/s of device memory
    hbm_bytes: float  # bytes of device memory
    source: str


PEAKS: dict[str, Peak] = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12,
        hbm_bytes_s=819e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM at 819 GB/s per chip",
    ),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}"
        ) from None

"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s in bf16, 393 TOP/s in
int8, 16 GB of HBM2e at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_bytes_per_s: float


DEVICE_PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9, ici_bytes_per_s=200e9),
}


def device_peaks(device_kind: str) -> Peaks:
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(DEVICE_PEAKS)}")
    return DEVICE_PEAKS[device_kind]

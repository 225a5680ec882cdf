"""Published peaks of the chips the benchmark may run on, keyed by
``jax.Device.device_kind``.  A device missing here is an error: no
share of a peak is ever computed against a guessed one.

Source for the TPU v5e row: Google Cloud documentation, "TPU v5e"
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    hbm_bytes_s: float     # bytes/s
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_s=819e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

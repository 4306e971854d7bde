"""Published peaks of each accelerator, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
A device kind that is not in this table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: float       # bytes of device memory
    source: str


V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_s=819e9,
            hbm_bytes=16e9,
            source='Google Cloud documentation, "TPU v5e"')

TABLE = {
    "TPU v5 lite": V5E,
    "TPU v5e": V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to bench/peaks.py with their source") from None

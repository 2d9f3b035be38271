"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A kind that is not here is an error:
no roofline or utilization is ever computed against a guessed peak.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e"
system architecture page: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at
819 GB/s per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    bf16_flops_per_s: float
    int8_ops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


_TABLE = {
    "TPU v5 lite": Peaks(
        kind="TPU v5 lite", bf16_flops_per_s=197e12, int8_ops_per_s=393e12,
        hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, TPU v5e system architecture"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return _TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(_TABLE)}") from None

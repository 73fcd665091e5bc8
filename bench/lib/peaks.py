"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

A copy kept with the benchmark (the program has its own table), so that no
change to the program can move the yardstick. A chip that is not in the
table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float     # FLOP/s, dense bf16 matmul
    hbm_bw: float         # bytes/s
    hbm_bytes: float      # device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s'),
}


def peaks_for(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports.  A device missing here is an error: the
benchmark has no default chip.

TPU v5e (JAX reports "TPU v5 lite"): 197 TFLOP/s bf16, 16 GB of HBM at
819 GB/s (Google Cloud documentation, "TPU v5e").
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class UnknownDevice(RuntimeError):
    pass


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

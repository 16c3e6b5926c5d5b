"""Peaks of the chip, and the least time of a call's operations and bytes.

Each model family (``bench/models/<name>.py``) computes the operations and
bytes of its served kernels from shapes alone, as a lower bound on what a
call needs: the multiply-adds of every convolution and matrix product (2
operations each), and the bytes of the call's inputs, weights and outputs
read or written once (``F32`` bytes a float).  Elementwise work, NMS and
the bilinear taps are not counted, and neither is any intermediate, so a
kernel's roofline share (least time / measured time) can only be
understated.
"""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s.  Keyed by jax's device_kind.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}
PEAKS_SOURCE = "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM"

F32 = 4


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_time(flops: float, nbytes: float, device_kind: str):
    """(seconds, bound) of the roofline: the larger of the two times."""
    p = peaks(device_kind)
    tc, tm = flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")

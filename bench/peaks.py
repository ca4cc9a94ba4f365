"""Published peaks per chip, keyed by JAX's `device_kind`.

A device kind that is not in the table is an error, never a default: a
roofline share against the wrong chip's peaks would be meaningless.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_bf16: float     # FLOP/s, dense bf16 matrix units
    hbm_bytes: float      # bytes/s, HBM bandwidth
    hbm_capacity: int     # bytes of HBM on one chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bytes=819e9, hbm_capacity=16 * 10**9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to bench/peaks.py with their source") from None


def least_time_s(flops: float, nbytes: float, pk: Peaks) -> tuple[float, str]:
    """The roofline's least time for ``flops`` and ``nbytes`` of HBM
    traffic, and which bound sets it ("compute" or "memory")."""
    tc, tm = flops / pk.flops_bf16, nbytes / pk.hbm_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")

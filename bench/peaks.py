"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Kept with the benchmark so that no change to the program can move the
yardstick. Source: Google Cloud documentation, "TPU v5e" (per chip:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). A kind that is
not listed is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_per_s: float  # matrix unit, bf16
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peaks(flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind={device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}"
        ) from None


def roofline_s(flops: float, nbytes: float, device_kind: str) -> tuple:
    """Least time the chip could take for the work, and what bounds it:
    ``(seconds, "flops" | "bytes")``."""
    p = peaks(device_kind)
    t_flops = flops / p.flops_per_s
    t_bytes = nbytes / p.hbm_bytes_per_s
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")

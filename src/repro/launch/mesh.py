"""Production mesh construction.

Single pod : (16, 16)   ("data", "model")  = 256 chips (TPU v5e pod)
Multi-pod  : (2, 16, 16)("pod", "data", "model") = 512 chips, "pod" over DCN.

Functions (not module constants) so importing never touches device state.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, found {len(devices)} — dryrun.py must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import"
        )
    dev = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    """Arbitrary mesh over the first prod(shape) devices (tests, elasticity)."""
    n = math.prod(shape)
    dev = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)


class DevicePeaks(NamedTuple):
    """Published per-chip peaks: bf16 matmul FLOP/s and HBM bytes/s."""

    flops_bf16: float
    hbm_bytes_per_s: float


#: Per-chip peaks keyed by ``jax.Device.device_kind``. Source: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": DevicePeaks(flops_bf16=197e12, hbm_bytes_per_s=819e9),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks of one chip of ``device_kind``; an unlisted kind raises rather
    than borrowing another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind={device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}"
        ) from None

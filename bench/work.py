"""Useful work of one grid build, and the chip peaks it is measured against.

The work is the algorithm's, not the implementation's: PB-SYM's operations
per point and the bytes a build cannot avoid moving (the float32 grid
written once, the points read once). Padding, overlap copies, parked slots
and halos never count, so the same build is credited with the same work
whichever path computes it.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float              # operations per second, the chip's highest
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


# Keyed by ``jax.Device.device_kind``. A kind missing here is an error.
PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; add "
                         "them to bench/work.py with their source") from None


def point_flops(Hs: int, Ht: int) -> float:
    """PB-SYM operations for one point: the (2Hs+1)^2 disk of spatial
    kernel values (10 each), the 2Ht+1 bar of temporal values (5 each), and
    the outer product's multiply-add over the cylinder (2 each)."""
    disk = (2 * Hs + 1) ** 2
    bar = 2 * Ht + 1
    return disk * 10.0 + bar * 5.0 + disk * bar * 2.0


def build_flops(n: int, Hs: int, Ht: int) -> float:
    return n * point_flops(Hs, Ht)


def build_bytes(n: int, grid_shape) -> float:
    """The float32 grid written once plus the (n, 3) float32 points read
    once."""
    gx, gy, gt = grid_shape
    return gx * gy * gt * 4.0 + n * 3 * 4.0


def least_time(flops: float, nbytes: float, peaks: Peaks, chips: int):
    """(seconds, bound): the least time ``chips`` chips could take, and
    which of "compute" or "memory" sets it."""
    t_c = flops / (chips * peaks.flops)
    t_m = nbytes / (chips * peaks.hbm_bytes_per_s)
    return (t_m, "memory") if t_m >= t_c else (t_c, "compute")

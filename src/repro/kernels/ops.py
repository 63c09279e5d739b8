"""Jit'd public wrappers around the Pallas STKDE kernels.

``stkde_tiled(points, dom)`` is the TPU performance path for single-device
STKDE: host-side overlap bucketing, each tile's point copies packed into
panels of ``chunk`` slots (``bucketing.bucket_points_panels``) -> Pallas
tile-GEMM kernel, one grid step per panel -> slice to the domain grid. It
compiles the kernel for the TPU; CPU callers ask for ``mode="interpret"``
(bitwise-faithful to the kernel body, slow) — use ``core.pb`` for fast CPU
execution.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.geometry import Domain
from repro.core import bucketing
from repro.core import kernels_math as km
from repro.obs import trace as obs_trace
from .stkde_tile import CHUNK, stkde_tiles_pallas


def default_tile(dom: Domain) -> Tuple[int, int, int]:
    """Tile shape tuned for the TPU memory hierarchy.

    * bx, by multiples of 8: bx*by is the output block's lane dimension
      (the GEMM's "N"), 1024 at the default 32x32.
    * bt (the "M" dimension, the output block's sublanes) a multiple of 8;
      temporal bandwidths are small so bt stays modest and the accumulator
      (bt*bx*by*4B) fits VMEM easily.
    """
    bx = int(min(bucketing.round_up(dom.Gx, 8), 32))
    by = int(min(bucketing.round_up(dom.Gy, 8), 32))
    bt = int(min(bucketing.round_up(dom.Gt, 8), 16))
    return (bx, by, bt)


def tiled_inputs(
    points: np.ndarray,
    dom: Domain,
    tile: Optional[Tuple[int, int, int]] = None,
    chunk: int = CHUNK,
) -> Tuple[np.ndarray, np.ndarray, Tuple[int, int, int]]:
    """Host-side kernel input: ``(lanes, tile_of, tile)``, the points packed
    into panels of ``chunk`` slots and each panel's tile."""
    if tile is None:
        tile = default_tile(dom)
    lanes, tile_of = bucketing.bucket_points_panels(points, dom, tile, chunk)
    return lanes, tile_of, tile


def stkde_tiled(
    points: np.ndarray,
    dom: Domain,
    tile: Optional[Tuple[int, int, int]] = None,
    chunk: int = CHUNK,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    mode: str = "compiled",
) -> jnp.ndarray:
    """STKDE density grid via the tiled PB-SYM GEMM kernel.

    ``chunk`` is the panel size, the points of one grid step. ``mode``
    ("compiled" | "interpret") selects how the Pallas kernel executes —
    see ``stkde_tiles_pallas``. Compiled mode needs a TPU. Opens
    ``stkde.tiled`` with the phases a mesh strategy names (``.bucket``,
    ``.dispatch``, ``.reassemble``).
    """
    pts = np.asarray(points, dtype=np.float32)
    with obs_trace.span("stkde.tiled", n=len(pts)):
        with obs_trace.span("stkde.tiled.bucket"):
            lanes, tile_of, tile = tiled_inputs(pts, dom, tile, chunk)
            with obs_trace.span("transfer.to_device",
                                bytes=lanes.nbytes + tile_of.nbytes):
                lanes, tile_of = jnp.asarray(lanes), jnp.asarray(tile_of)
        with obs_trace.span("stkde.tiled.dispatch"):
            padded = stkde_tiles_pallas(lanes, tile_of, dom, tile,
                                        len(pts), ks, kt, mode=mode)
        with obs_trace.span("stkde.tiled.reassemble"):
            return padded[: dom.Gx, : dom.Gy, : dom.Gt]

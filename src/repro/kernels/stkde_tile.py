"""Pallas TPU kernel: PB-SYM tile accumulation as an MXU contraction.

The paper's PB-SYM observation — each point's contribution factors into a
spatial disk ``Ks[X, Y]`` and a temporal bar ``Kt[T]`` — is, on TPU, a
*structure*-exposing trick: for a grid tile and a panel of P candidate
points,

    density[bx, by, bt]  =  sum_p Ks_p[bx, by] * Kt_p[bt]
                         =  reshape( Kt  @  Ksᵀ )
                            with Kt: (bt, P), Ks: (bx*by, P)

i.e. a GEMM contracting over the *point* dimension, executed on the MXU
instead of a scalar scatter loop. Layout (Mosaic's (8, 128) block rule and
the VMEM limit hold at the paper's Table 2 sizes):

  * candidate points arrive pre-bucketed per tile (host-side, DD-style
    overlap bucketing — ``core/bucketing.py``) with the points on the
    lanes: a ``(3, chunk)`` block of x/y/t rows per grid step. Invalid
    slots are parked far outside the domain, where every kernel is zero,
    so no validity mask reaches the kernel;
  * the point chunks stream through the innermost ``"arbitrary"`` grid
    axis, so VMEM holds one chunk whatever the bucket capacity is;
  * the output block ``(bt, bx*by)`` stays resident across that axis and
    accumulates (the paper's DD "cache fitting" insight, made explicit);
    the output is ``(ntx, nty, T, bx*by)``, reassembled into ``(X, Y, T)``
    outside the kernel.

Grid = (ntx, nty, ntt, nchunks); the three tile axes are embarrassingly
parallel.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bucketing import PARK
from repro.core.geometry import Domain
from repro.core import kernels_math as km

MODES = ("interpret", "compiled")
CHUNK = 512   # candidate points per grid step (lanes of the point block)


def _kernel(pts_ref, out_ref, *, dom: Domain, tile: Tuple[int, int, int],
            norm: float, ks, kt):
    """One (tile, chunk) grid step: ``out += Kt(chunk) @ Ks(chunk)ᵀ``.

    pts_ref: (3, chunk) rows x, y, t of this chunk's candidate points.
    out_ref: (bt, bx*by) accumulator of this tile, column c = x * by + y.
    """
    bx, by, bt = tile

    @pl.when(pl.program_id(3) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    x0 = (pl.program_id(0) * bx).astype(jnp.float32)
    y0 = (pl.program_id(1) * by).astype(jnp.float32)
    t0 = (pl.program_id(2) * bt).astype(jnp.float32)
    # (x, y) of every output column, from a 2-D iota (TPU needs >= 2-D)
    r = jax.lax.broadcasted_iota(jnp.int32, (bx * by, 1), 0).astype(
        jnp.float32)
    ix = jnp.floor((r + 0.5) * (1.0 / by))
    iy = r - ix * by
    it = jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0).astype(jnp.float32)
    xc = dom.ox + (x0 + ix + 0.5) * dom.sres             # (bx*by, 1)
    yc = dom.oy + (y0 + iy + 0.5) * dom.sres             # (bx*by, 1)
    tc = dom.ot + (t0 + it + 0.5) * dom.tres             # (bt, 1)

    u = (xc - pts_ref[0:1, :]) / dom.hs                  # (bx*by, chunk)
    v = (yc - pts_ref[1:2, :]) / dom.hs                  # (bx*by, chunk)
    w = (tc - pts_ref[2:3, :]) / dom.ht                  # (bt, chunk)
    Ks = ks(u, v) * norm
    Kt = kt(w)
    # MXU contraction over the point (lane) dimension of both panels
    out_ref[...] += jax.lax.dot_general(
        Kt, Ks, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def lane_layout(points: np.ndarray, valid: np.ndarray,
                chunk: int) -> Tuple[np.ndarray, int]:
    """Kernel input from capacity-padded buckets, and the chunk to use.

    ``points`` (ntx, nty, ntt, cap, 3) and ``valid`` (ntx, nty, ntt, cap)
    become one (ntx, nty, ntt, 3, cap_p) array with the points on the
    lanes and invalid slots parked at ``PARK``. A bucket smaller than
    ``chunk`` becomes one chunk spanning the whole (8-aligned) bucket;
    otherwise cap_p is padded to a multiple of ``chunk``. Either way the
    point block's lane dimension is the whole array or ``chunk``, so a
    multiple-of-128 ``chunk`` meets Mosaic's block rule. Built on the host:
    on the device, a (..., cap, 3) array pads its 3 coordinates to 128
    lanes.
    """
    cap = points.shape[3]
    cap_p = -(-max(cap, 1) // 8) * 8
    if cap_p > chunk:
        cap_p = -(-cap // chunk) * chunk
    else:
        chunk = cap_p
    out = np.full(points.shape[:3] + (3, cap_p), PARK, dtype=np.float32)
    out[..., :cap] = np.where(valid[..., None], points, PARK).swapaxes(-1, -2)
    return out, chunk


@functools.partial(
    jax.jit,
    static_argnames=("dom", "tile", "n_total", "chunk", "ks", "kt", "mode"),
)
def stkde_tiles_pallas(
    pts_lanes: jnp.ndarray,    # (ntx, nty, ntt, 3, cap_p) f32, lane_layout
    dom: Domain,
    tile: Tuple[int, int, int],
    n_total: int,
    chunk: int,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    mode: str = "compiled",
) -> jnp.ndarray:
    """Padded density grid (ntx*bx, nty*by, ntt*bt) from bucketed points.

    ``mode="compiled"`` lowers through Mosaic, the TPU kernel compiler, and
    fails on any other backend; ``mode="interpret"`` runs the kernel body
    under the Pallas interpreter (any backend, slow) and is what CPU tests
    ask for.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    ntx, nty, ntt, _, cap_p = pts_lanes.shape
    bx, by, bt = tile
    kernel = functools.partial(
        _kernel, dom=dom, tile=tile,
        norm=km.normalization(n_total, dom.hs, dom.ht), ks=ks, kt=kt)
    out = pl.pallas_call(
        kernel,
        grid=(ntx, nty, ntt, cap_p // chunk),
        in_specs=[pl.BlockSpec((None, None, None, 3, chunk),
                               lambda i, j, k, c: (i, j, k, 0, c))],
        out_specs=pl.BlockSpec((None, None, bt, bx * by),
                               lambda i, j, k, c: (i, j, k, 0)),
        out_shape=jax.ShapeDtypeStruct((ntx, nty, ntt * bt, bx * by),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=mode == "interpret",
        name="stkde_tile",
    )(pts_lanes)
    # (ntx, nty, T, bx*by) -> (X, Y, T); every intermediate keeps a minor
    # dimension the TPU's (8, 128) tiled layout stores without padding
    out = jnp.swapaxes(out, 2, 3).reshape(ntx, nty, bx, by, ntt * bt)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(
        ntx * bx, nty * by, ntt * bt)

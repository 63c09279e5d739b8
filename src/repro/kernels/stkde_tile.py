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

  * candidate points arrive packed into panels (host-side, DD-style
    overlap bucketing — ``core/bucketing.py::bucket_points_panels``): one
    ``(3, npanels * chunk)`` array with the points on the lanes, each
    tile's copies in whole panels of ``chunk`` slots, tiles back to back.
    A ``(3, chunk)`` block of x/y/t rows is one grid step. Unused slots
    are parked far outside the domain, where every kernel is zero, so no
    validity mask reaches the kernel;
  * an ``(npanels,)`` table gives each panel's flat tile id. It is
    scalar-prefetched, so the output block's index map reads it: the
    grid runs over the panels alone, one step per panel, and a tile costs
    the panels its own load needs;
  * each tile's panels are one run of consecutive steps, so the tile's
    output block ``(bt, bx*by)`` stays resident across them and
    accumulates (the paper's DD "cache fitting" insight, made explicit),
    is zeroed on the run's first panel and written back once; the output
    is ``(ntiles, bt, bx*by)``, the memory order of ``(ntx, nty, T,
    bx*by)``, reassembled into ``(X, Y, T)`` outside the kernel.

Grid = (npanels,), ``"arbitrary"``: consecutive panels of one tile add
into one block.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bucketing import num_tiles
from repro.core.geometry import Domain
from repro.core import kernels_math as km

MODES = ("interpret", "compiled")
CHUNK = 512   # slots of a panel: the points of one grid step (lanes)


def _kernel(tile_of_ref, pts_ref, out_ref, *, dom: Domain,
            tile: Tuple[int, int, int], nt: Tuple[int, int, int],
            norm: float, ks, kt):
    """One panel's grid step: ``out += Kt(panel) @ Ks(panel)ᵀ``.

    tile_of_ref: (npanels,) flat tile id of every panel (scalar prefetch).
    pts_ref: (3, chunk) rows x, y, t of this panel's candidate points.
    out_ref: (bt, bx*by) accumulator of the panel's tile, column
    c = x * by + y.
    """
    bx, by, bt = tile
    _, nty, ntt = nt
    p = pl.program_id(0)
    f = tile_of_ref[p]

    @pl.when((p == 0) | (tile_of_ref[jnp.maximum(p - 1, 0)] != f))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    x0 = (f // (nty * ntt) * bx).astype(jnp.float32)
    y0 = (f // ntt % nty * by).astype(jnp.float32)
    t0 = (f % ntt * bt).astype(jnp.float32)
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


@functools.partial(
    jax.jit,
    static_argnames=("dom", "tile", "n_total", "ks", "kt", "mode"),
)
def stkde_tiles_pallas(
    lanes: jnp.ndarray,        # (3, npanels * chunk) f32, panel-packed
    tile_of: jnp.ndarray,      # (npanels,) int32, each panel's flat tile
    dom: Domain,
    tile: Tuple[int, int, int],
    n_total: int,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    mode: str = "compiled",
) -> jnp.ndarray:
    """Padded density grid (ntx*bx, nty*by, ntt*bt) from panel-packed
    points (``bucketing.bucket_points_panels``).

    The panel size is ``lanes``' width over the panel count; a multiple of
    128 meets Mosaic's block rule. ``mode="compiled"`` lowers through
    Mosaic, the TPU kernel compiler, and fails on any other backend;
    ``mode="interpret"`` runs the kernel body under the Pallas interpreter
    (any backend, slow) and is what CPU tests ask for.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    (npanels,) = tile_of.shape
    chunk = lanes.shape[1] // npanels
    nt = num_tiles(dom, tile)
    ntx, nty, ntt = nt
    bx, by, bt = tile
    kernel = functools.partial(
        _kernel, dom=dom, tile=tile, nt=nt,
        norm=km.normalization(n_total, dom.hs, dom.ht), ks=ks, kt=kt)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(npanels,),
            in_specs=[pl.BlockSpec((3, chunk), lambda p, tile_of: (0, p))],
            out_specs=pl.BlockSpec((None, bt, bx * by),
                                   lambda p, tile_of: (tile_of[p], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((ntx * nty * ntt, bt, bx * by),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=mode == "interpret",
        name="stkde_tile",
    )(tile_of, lanes)
    # (ntiles, bt, bx*by) is (ntx, nty, T, bx*by) in memory -> (X, Y, T);
    # every intermediate keeps a minor dimension the TPU's (8, 128) tiled
    # layout stores without padding
    out = out.reshape(ntx, nty, ntt * bt, bx * by)
    out = jnp.swapaxes(out, 2, 3).reshape(ntx, nty, bx, by, ntt * bt)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(
        ntx * bx, nty * by, ntt * bt)

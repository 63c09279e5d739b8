"""Pure-jnp oracles for the Pallas STKDE kernels.

``stkde_tiles_ref`` computes exactly what the tile kernel computes — per-tile
density via the PB-SYM separable contraction, from the same panel-packed
input, summed per tile by the panel -> tile table — with plain jnp ops. It
is the allclose target for every kernel sweep test, and is itself
cross-validated against ``core.pb``/``core.vb`` (three independent
formulations).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bucketing import num_tiles
from repro.core.geometry import Domain
from repro.core import kernels_math as km


@functools.partial(
    jax.jit,
    static_argnames=("dom", "tile", "n_total", "ks", "kt"),
)
def stkde_tiles_ref(
    lanes: jnp.ndarray,       # (3, npanels * chunk) f32, parked where empty
    tile_of: jnp.ndarray,     # (npanels,) int32, each panel's flat tile
    dom: Domain,
    tile: tuple,
    n_total: int,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
) -> jnp.ndarray:
    """Padded density grid (ntx*bx, nty*by, ntt*bt); slice to dom.grid_shape.

    Each panel's contribution to its tile, summed over the panels of a
    tile by the panel -> tile table."""
    bx, by, bt = tile
    ntx, nty, ntt = num_tiles(dom, tile)
    norm = km.normalization(n_total, dom.hs, dom.ht)
    (npanels,) = tile_of.shape
    panels = lanes.reshape(3, npanels, -1).transpose(1, 0, 2)

    ix = jnp.arange(bx, dtype=jnp.float32)
    iy = jnp.arange(by, dtype=jnp.float32)
    it = jnp.arange(bt, dtype=jnp.float32)

    def one_panel(f, pts):
        ti = (f // (nty * ntt)).astype(jnp.float32)
        tj = (f // ntt % nty).astype(jnp.float32)
        tk = (f % ntt).astype(jnp.float32)
        xc = dom.ox + ((ti * bx + ix) + 0.5) * dom.sres
        yc = dom.oy + ((tj * by + iy) + 0.5) * dom.sres
        tc = dom.ot + ((tk * bt + it) + 0.5) * dom.tres
        u = (xc[None, :] - pts[0][:, None]) / dom.hs     # (chunk, bx)
        v = (yc[None, :] - pts[1][:, None]) / dom.hs     # (chunk, by)
        w = (tc[None, :] - pts[2][:, None]) / dom.ht     # (chunk, bt)
        Ks = ks(u[:, :, None], v[:, None, :]) * norm     # (chunk, bx, by)
        Kt = kt(w)                                       # (chunk, bt)
        return jnp.einsum("pxy,pt->xyt", Ks, Kt,
                          precision=jax.lax.Precision.HIGHEST)

    tiles = jax.ops.segment_sum(jax.vmap(one_panel)(tile_of, panels),
                                tile_of, num_segments=ntx * nty * ntt)
    tiles = tiles.reshape(ntx, nty, ntt, bx, by, bt)
    return jnp.transpose(tiles, (0, 3, 1, 4, 2, 5)).reshape(
        ntx * bx, nty * by, ntt * bt
    )

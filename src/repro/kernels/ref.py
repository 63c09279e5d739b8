"""Pure-jnp oracles for the Pallas STKDE kernels.

``stkde_tiles_ref`` computes exactly what the tile kernel computes — per-tile
density via the PB-SYM separable contraction, from the same lane-layout
input — with plain jnp ops. It is the allclose target for every kernel sweep
test, and is itself cross-validated against ``core.pb``/``core.vb`` (three
independent formulations).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.geometry import Domain
from repro.core import kernels_math as km


@functools.partial(
    jax.jit,
    static_argnames=("dom", "tile", "n_total", "ks", "kt"),
)
def stkde_tiles_ref(
    pts_lanes: jnp.ndarray,   # (ntx, nty, ntt, 3, cap) f32, invalid parked
    dom: Domain,
    tile: tuple,
    n_total: int,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
) -> jnp.ndarray:
    """Padded density grid (ntx*bx, nty*by, ntt*bt); slice to dom.grid_shape."""
    bx, by, bt = tile
    ntx, nty, ntt = pts_lanes.shape[:3]
    norm = km.normalization(n_total, dom.hs, dom.ht)

    ix = jnp.arange(bx, dtype=jnp.float32)
    iy = jnp.arange(by, dtype=jnp.float32)
    it = jnp.arange(bt, dtype=jnp.float32)

    def one_tile(ti, tj, tk, pts):
        xc = dom.ox + ((ti * bx + ix) + 0.5) * dom.sres
        yc = dom.oy + ((tj * by + iy) + 0.5) * dom.sres
        tc = dom.ot + ((tk * bt + it) + 0.5) * dom.tres
        u = (xc[None, :] - pts[0][:, None]) / dom.hs     # (cap, bx)
        v = (yc[None, :] - pts[1][:, None]) / dom.hs     # (cap, by)
        w = (tc[None, :] - pts[2][:, None]) / dom.ht     # (cap, bt)
        Ks = ks(u[:, :, None], v[:, None, :]) * norm     # (cap, bx, by)
        Kt = kt(w)                                       # (cap, bt)
        return jnp.einsum("pxy,pt->xyt", Ks, Kt,
                          precision=jax.lax.Precision.HIGHEST)

    f = jax.vmap(
        jax.vmap(
            jax.vmap(one_tile, in_axes=(None, None, 0, 0)),
            in_axes=(None, 0, None, 0),
        ),
        in_axes=(0, None, None, 0),
    )
    tiles = f(
        jnp.arange(ntx, dtype=jnp.float32),
        jnp.arange(nty, dtype=jnp.float32),
        jnp.arange(ntt, dtype=jnp.float32),
        pts_lanes,
    )                                                    # (ntx,nty,ntt,bx,by,bt)
    return jnp.transpose(tiles, (0, 3, 1, 4, 2, 5)).reshape(
        ntx * bx, nty * by, ntt * bt
    )

"""Point-based STKDE algorithms: PB, PB-DISK, PB-BAR, PB-SYM.

Algorithm 2/3 of the paper: stream over points, each point scatter-adds its
bandwidth cylinder into the grid. The four variants differ in how much of the
kernel evaluation is hoisted out of the cylinder loop:

  PB       evaluates ks*kt per cylinder voxel              (no hoisting)
  PB-DISK  hoists the spatial invariant Ks[X,Y]            (Algorithm 3, half)
  PB-BAR   hoists the temporal invariant Kt[T]
  PB-SYM   hoists both; cylinder work is a pure outer product Ks ⊗ Kt

All variants produce identical grids; they exist separately to reproduce the
flop-reduction story of the paper's Table 3. The redundant work in
PB / PB-DISK / PB-BAR is expressed through *materialized* broadcasts so XLA
actually performs it.

This module is the readable reference & CPU execution path; the TPU
performance path is ``repro.kernels`` (tile GEMM). Both are cross-tested.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .geometry import Domain
from . import kernels_math as km

VARIANTS = ("pb", "disk", "bar", "sym")


def _cylinder_values(
    pts: jnp.ndarray,  # (B, 3)
    vox: jnp.ndarray,  # (B, 3) int32 home voxels
    dom: Domain,
    variant: str,
    ks: km.SpatialKernel,
    kt: km.TemporalKernel,
    n_total: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Kernel values + linear indices for a block of points.

    Returns (lin_idx, vals), both (B, Dx*Dy*Dt). Out-of-grid voxels get
    lin_idx == grid_size (dropped by the scatter's mode='drop').
    """
    Hs, Ht = dom.Hs, dom.Ht
    Dx = Dy = 2 * Hs + 1
    Dt = 2 * Ht + 1
    B = pts.shape[0]
    Gx, Gy, Gt = dom.grid_shape
    gsz = Gx * Gy * Gt

    dx = jnp.arange(-Hs, Hs + 1)
    dt = jnp.arange(-Ht, Ht + 1)
    X = vox[:, 0:1] + dx[None, :]                    # (B, Dx)
    Y = vox[:, 1:2] + dx[None, :]                    # (B, Dy)
    T = vox[:, 2:3] + dt[None, :]                    # (B, Dt)

    # voxel-center coordinates of the cylinder bbox
    xc = dom.ox + (X.astype(jnp.float32) + 0.5) * dom.sres
    yc = dom.oy + (Y.astype(jnp.float32) + 0.5) * dom.sres
    tc = dom.ot + (T.astype(jnp.float32) + 0.5) * dom.tres
    u = (xc - pts[:, 0:1]) / dom.hs                  # (B, Dx)
    v = (yc - pts[:, 1:2]) / dom.hs                  # (B, Dy)
    w = (tc - pts[:, 2:3]) / dom.ht                  # (B, Dt)

    norm = km.normalization(n_total, dom.hs, dom.ht)
    shape3 = (B, Dx, Dy, Dt)

    def _pin(x):
        """Materialize a broadcast for real.

        XLA sinks broadcasts through elementwise chains — i.e. the compiler
        performs the paper's DISK/BAR/SYM hoisting automatically, which
        would make all four variants compile to the same program. The
        barrier pins the broadcast so each variant performs the flops the
        scalar algorithm it models would perform (Table-3 benchmark
        fidelity; results are bit-identical either way).
        """
        return jax.lax.optimization_barrier(x)

    if variant == "sym":
        Ks = ks(u[:, :, None], v[:, None, :]) * norm         # (B, Dx, Dy)
        Kt = kt(w)                                           # (B, Dt)
        vals = Ks[:, :, :, None] * Kt[:, None, None, :]
    elif variant == "disk":
        Ks = ks(u[:, :, None], v[:, None, :]) * norm
        W = _pin(jnp.broadcast_to(w[:, None, None, :], shape3))
        vals = Ks[:, :, :, None] * kt(W)
    elif variant == "bar":
        Kt = kt(w) * norm
        U = _pin(jnp.broadcast_to(u[:, :, None, None], shape3))
        V = _pin(jnp.broadcast_to(v[:, None, :, None], shape3))
        vals = ks(U, V) * Kt[:, None, None, :]
    elif variant == "pb":
        U = _pin(jnp.broadcast_to(u[:, :, None, None], shape3))
        V = _pin(jnp.broadcast_to(v[:, None, :, None], shape3))
        W = _pin(jnp.broadcast_to(w[:, None, None, :], shape3))
        vals = ks(U, V) * kt(W) * norm
    else:
        raise ValueError(f"unknown variant {variant!r}")

    # linear indices with out-of-bounds -> gsz (dropped)
    okx = (X >= 0) & (X < Gx)
    oky = (Y >= 0) & (Y < Gy)
    okt = (T >= 0) & (T < Gt)
    px = jnp.where(okx, X * (Gy * Gt), gsz)
    py = jnp.where(oky, Y * Gt, gsz)
    ptt = jnp.where(okt, T, gsz)
    lin = (
        px[:, :, None, None] + py[:, None, :, None] + ptt[:, None, None, :]
    )
    lin = jnp.minimum(lin, gsz)                      # keep within drop range
    return lin.reshape(B, -1), vals.reshape(B, -1)


def _block_size(dom: Domain, budget_elems: int) -> int:
    per_point = dom.cylinder_voxels
    return max(1, min(4096, budget_elems // max(1, per_point)))


@functools.partial(
    jax.jit,
    static_argnames=(
        "dom", "variant", "ks", "kt", "budget_elems", "n_total"
    ),
)
def _pb_impl(
    points: jnp.ndarray,
    dom: Domain,
    variant: str,
    ks,
    kt,
    budget_elems: int,
    n_total: int = None,
) -> jnp.ndarray:
    n = points.shape[0]
    n_norm = n if n_total is None else n_total
    gsz = dom.grid_voxels
    if gsz >= 2**30:
        raise ValueError(
            "scatter-path PB needs grid < 2^30 voxels; use the tiled kernel "
            "or the distributed strategies for larger grids"
        )
    B = _block_size(dom, budget_elems)
    nblocks = -(-n // B)
    pad = nblocks * B - n
    pts = jnp.pad(points.astype(jnp.float32), ((0, pad), (0, 0)))
    # padded points are parked outside every grid cylinder via a huge coord
    if pad:
        far = jnp.float32(dom.ox - 1e8)
        pts = pts.at[n:, 0].set(far)
    # Unclipped home voxels: points outside this (possibly local) domain
    # still contribute the in-domain part of their cylinder; fully
    # out-of-reach voxels are dropped by the scatter.
    vox = dom.point_voxels_unclipped(pts)
    pts_b = pts.reshape(nblocks, B, 3)
    vox_b = vox.reshape(nblocks, B, 3)

    grid = jnp.zeros((gsz + 1,), dtype=jnp.float32)  # +1 slot absorbs drops
    # Inside shard_map the scan carry must carry the same varying-manual-axes
    # tag as the point shards feeding it.
    vma = jax.typeof(points).vma
    if vma:
        grid = jax.lax.pcast(grid, tuple(vma), to="varying")

    def body(grid, blk):
        p, v = blk
        lin, vals = _cylinder_values(p, v, dom, variant, ks, kt, n_norm)
        return grid.at[lin.reshape(-1)].add(
            vals.reshape(-1), mode="drop"
        ), None

    grid, _ = jax.lax.scan(body, grid, (pts_b, vox_b))
    return grid[:gsz].reshape(dom.grid_shape)


def pb(points, dom: Domain, variant: str = "sym",
       ks: km.SpatialKernel = km.DEFAULT_KS,
       kt: km.TemporalKernel = km.DEFAULT_KT,
       budget_elems: int = 1 << 22,
       n_total: int = None) -> jnp.ndarray:
    """Point-based STKDE. ``variant`` in {"pb", "disk", "bar", "sym"}.

    ``n_total`` overrides the normalization count (distributed callers pass
    the global point count while supplying only their local shard).
    """
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}")
    return _pb_impl(
        jnp.asarray(points), dom, variant, ks, kt, budget_elems, n_total
    )


def pb_sym(points, dom: Domain, **kw) -> jnp.ndarray:
    return pb(points, dom, variant="sym", **kw)

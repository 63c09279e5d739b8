"""Multi-device STKDE strategies (shard_map) — the paper's §4/§5 on a TPU mesh.

Strategy map (paper strategy -> TPU mesh layout):

  stkde_dr      PB-SYM-DR   points sharded over all devices, per-device full
                            grid, all-reduce. Pleasingly parallel; comm = grid.
  stkde_dd      PB-SYM-DD   grid block-sharded over a 2-D device grid; points
                            overlap-bucketed (cut-cylinder work overhead);
                            ZERO communication.
  stkde_pd      PB-SYM-PD   work-efficient owner-computes: points home-
                            bucketed, each device computes a halo-extended
                            local grid, halos folded into neighbors with
                            ppermute (races -> halo exchange).
  stkde_dd_lpt  PB-SYM-PD-SCHED   fine tiles, LPT load-aware placement
                            (scheduling -> placement), tile-soup assembly.
  stkde_hybrid  PB-SYM-PD-REP     mesh factored (rep × workers): each
                            bucket's points dealt over the rep axis, PD per
                            slice, psum over rep only. r=1 ⇒ PD, r=P ⇒ DR.

All strategies are normalization-consistent with ``core.pb`` (global n) and
are cross-tested for exact agreement in tests/test_stkde_distributed.py.

Every ``stkde_<s>`` opens the span ``stkde.<s>`` and names its phases the
same way: ``stkde.<s>.bucket`` (the points' host layout and their
``transfer.to_device``), ``stkde.<s>.dispatch`` (building the ``jax.jit``
and calling it: retrace, lowering, cache fetch, enqueue) and, where the
shards are put back together, ``stkde.<s>.reassemble``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.geometry import Domain
from repro.core import bucketing, kernels_math as km
from repro.core.pb import pb as _pb
from repro.obs import trace as obs_trace
from repro.resilience import faults as _faults
from . import partition

PARK = bucketing.PARK


def _pad_tile_grid(points, valid, A, B):
    """Pad bucket arrays to the full (A, B) device grid.

    ceil(G/A)*A can overshoot G, leaving fewer tiles than devices — the
    missing (edge) tiles are empty by construction."""
    na, nb = points.shape[:2]
    if na == A and nb == B:
        return points, valid
    pp = np.zeros((A, B) + points.shape[2:], points.dtype)
    vv = np.zeros((A, B) + valid.shape[2:], valid.dtype)
    pp[:na, :nb] = points
    vv[:na, :nb] = valid
    pp[vv == 0] = PARK
    return pp, vv


def _mesh_sizes(mesh: Mesh, axes) -> Tuple[int, ...]:
    return tuple(mesh.shape[a] for a in axes)


def _on_mesh(mesh: Mesh, lead, *arrays):
    """Place host arrays on ``mesh``, split over their leading dimensions
    (``lead`` mesh axes) as the strategy's shard_map expects, so each
    device receives only its own shard."""
    sharding = NamedSharding(mesh, P(*lead))
    with obs_trace.span("transfer.to_device",
                        bytes=sum(a.nbytes for a in arrays)):
        out = tuple(jax.device_put(a, sharding) for a in arrays)
    return out if len(out) > 1 else out[0]


def _assemble_xy(out, dom: Domain, A: int, B: int, gx_loc: int,
                 gy_loc: int):
    """The (A, B, gx_loc, gy_loc, Gt) device blocks as the (Gx, Gy, Gt)
    grid."""
    out = out.reshape(A, B, gx_loc, gy_loc, dom.Gt)
    out = out.transpose(0, 2, 1, 3, 4).reshape(A * gx_loc, B * gy_loc, dom.Gt)
    return out[: dom.Gx, : dom.Gy, :]


def _park_invalid(pts, valid):
    """Move invalid bucket slots far outside every domain."""
    return jnp.where(valid[..., None] > 0, pts, PARK)


# ------------------------------------------------------------------ DR
def prepare_dr(
    points: np.ndarray, dom: Domain, mesh: Mesh, axes
) -> jnp.ndarray:
    """Pad points to a multiple of the device count (PARK fills)."""
    pts = np.asarray(points, dtype=np.float32)
    n = len(pts)
    Ptot = int(np.prod(_mesh_sizes(mesh, axes)))
    npad = bucketing.round_up(max(n, Ptot), Ptot)
    full = np.full((npad, 3), PARK, dtype=np.float32)
    full[:n] = pts
    return _on_mesh(mesh, (tuple(axes),), full)


def stkde_dr(
    points: np.ndarray,
    dom: Domain,
    mesh: Mesh,
    axes: Tuple[str, ...] = ("data", "model"),
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    n_total: Optional[int] = None,
) -> jnp.ndarray:
    """Domain replication: shard points, replicate grid, all-reduce.

    ``n_total`` overrides the normalization count — chunked execution
    passes the *global* point count while feeding a chunk at a time.
    """
    n = int(n_total) if n_total is not None else len(points)
    with obs_trace.span("stkde.dr", n=n, mesh=str(dict(mesh.shape))):
        with obs_trace.span("stkde.dr.bucket"):
            full = prepare_dr(points, dom, mesh, axes)
        with obs_trace.span("stkde.dr.dispatch"):
            return build_dr(dom, mesh, axes, n, ks, kt)(full)


def build_dr(dom: Domain, mesh: Mesh, axes, n: int,
             ks=km.DEFAULT_KS, kt=km.DEFAULT_KT):
    """Jitted DR computation over pre-sharded points (dry-run lowerable)."""

    def f(local):  # (npad/P, 3)
        g = _pb(local, dom, variant="sym", ks=ks, kt=kt, n_total=n)
        return jax.lax.psum(g, axes)

    return jax.jit(shard_map(
        f, mesh=mesh, in_specs=P(axes), out_specs=P(None, None, None)
    ))


# ------------------------------------------------------------------ DD
def _device_grid_dims(dom: Domain, A: int, B: int) -> Tuple[int, int]:
    return (math.ceil(dom.Gx / A), math.ceil(dom.Gy / B))


def _local_domain(dom: Domain, gx_loc: int, gy_loc: int,
                  halo: int = 0) -> Domain:
    """A device-local domain at canonical origin (points are shifted)."""
    import dataclasses

    return dataclasses.replace(
        dom,
        gx=(gx_loc + 2 * halo) * dom.sres,
        gy=(gy_loc + 2 * halo) * dom.sres,
        gt=dom.Gt * dom.tres,
    )


def prepare_dd(
    points: np.ndarray, dom: Domain, mesh: Mesh, axes,
    cap: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Overlap-bucket points onto the (A, B) device grid (DD layout)."""
    A, B = _mesh_sizes(mesh, axes)
    pts = np.asarray(points, dtype=np.float32)
    gx_loc, gy_loc = _device_grid_dims(dom, A, B)
    b = bucketing.bucket_points_overlap(
        pts, dom, (gx_loc, gy_loc, dom.Gt), cap=cap
    )
    na, nb = b.ntiles[0], b.ntiles[1]
    bpts, bval = _pad_tile_grid(
        b.points.reshape(na, nb, b.cap, 3),
        b.valid.reshape(na, nb, b.cap).astype(np.float32), A, B)
    return _on_mesh(mesh, axes, bpts, bval)


def stkde_dd(
    points: np.ndarray,
    dom: Domain,
    mesh: Mesh,
    axes: Tuple[str, str] = ("data", "model"),
    cap: Optional[int] = None,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    n_total: Optional[int] = None,
) -> jnp.ndarray:
    """Domain decomposition: block-sharded grid, overlap-routed points."""
    A, B = _mesh_sizes(mesh, axes)
    n = int(n_total) if n_total is not None else len(points)
    gx_loc, gy_loc = _device_grid_dims(dom, A, B)
    with obs_trace.span("stkde.dd", n=n, mesh=str(dict(mesh.shape))):
        with obs_trace.span("stkde.dd.bucket"):
            bpts, bval = prepare_dd(points, dom, mesh, axes, cap=cap)
        with obs_trace.span("stkde.dd.dispatch"):
            out = build_dd(dom, mesh, axes, n, ks, kt)(bpts, bval)
        with obs_trace.span("stkde.dd.reassemble"):
            return _assemble_xy(out, dom, A, B, gx_loc, gy_loc)


def build_dd(dom: Domain, mesh: Mesh, axes, n: int,
             ks=km.DEFAULT_KS, kt=km.DEFAULT_KT):
    """Jitted DD over overlap-bucketed points (dry-run lowerable)."""
    ax, ay = axes
    A, B = _mesh_sizes(mesh, axes)
    gx_loc, gy_loc = _device_grid_dims(dom, A, B)
    ldom = _local_domain(dom, gx_loc, gy_loc)

    def f(pts_blk, val_blk):  # (1, 1, cap, 3), (1, 1, cap)
        i = jax.lax.axis_index(ax).astype(jnp.float32)
        j = jax.lax.axis_index(ay).astype(jnp.float32)
        p = _park_invalid(pts_blk[0, 0], val_blk[0, 0])
        shift = jnp.stack(
            [i * gx_loc * dom.sres, j * gy_loc * dom.sres, jnp.float32(0.0)]
        )
        g = _pb(p - shift, ldom, variant="sym", ks=ks, kt=kt, n_total=n)
        return g[None, None]  # (1, 1, gx_loc, gy_loc, Gt)

    return jax.jit(shard_map(
        f,
        mesh=mesh,
        in_specs=(P(ax, ay, None, None), P(ax, ay, None)),
        out_specs=P(ax, ay, None, None, None),
    ))


# ------------------------------------------------------------------ PD
def prepare_pd(
    points: np.ndarray, dom: Domain, mesh: Mesh, axes,
    cap: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Home-bucket points onto the (A, B) device grid (PD layout)."""
    A, B = _mesh_sizes(mesh, axes)
    pts = np.asarray(points, dtype=np.float32)
    gx_loc, gy_loc = _device_grid_dims(dom, A, B)
    b = bucketing.bucket_points_home(
        pts, dom, (gx_loc, gy_loc, dom.Gt), cap=cap
    )
    na, nb = b.ntiles[0], b.ntiles[1]
    bp, bv = _pad_tile_grid(
        b.points.reshape(na, nb, b.cap, 3),
        b.valid.reshape(na, nb, b.cap).astype(np.float32), A, B)
    return _on_mesh(mesh, axes, bp, bv)


def stkde_pd(
    points: np.ndarray,
    dom: Domain,
    mesh: Mesh,
    axes: Tuple[str, str] = ("data", "model"),
    cap: Optional[int] = None,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    n_total: Optional[int] = None,
    _rep_axis: Optional[str] = None,
) -> jnp.ndarray:
    """Work-efficient owner-computes + halo exchange (PB-SYM-PD)."""
    ax, ay = axes
    A, B = _mesh_sizes(mesh, axes)
    pts = np.asarray(points, dtype=np.float32)
    n = int(n_total) if n_total is not None else len(pts)
    gx_loc, gy_loc = _device_grid_dims(dom, A, B)
    Hs = dom.Hs
    if gx_loc < Hs or gy_loc < Hs:
        raise ValueError(
            f"PD requires subdomains >= bandwidth: local ({gx_loc},{gy_loc})"
            f" vs Hs={Hs}; use DD/DR or a coarser device grid"
            " (paper §5.1 constraint)"
        )
    strat = "pd" if _rep_axis is None else "hybrid"
    with obs_trace.span(f"stkde.{strat}", n=n, mesh=str(dict(mesh.shape))):
        with obs_trace.span(f"stkde.{strat}.bucket"):
            if _rep_axis is None:
                bpts, bval = prepare_pd(pts, dom, mesh, axes, cap=cap)
            else:  # (R, A, B, cap, 3), sharded over rep too
                bpts, bval = prepare_hybrid(pts, dom, mesh, axes,
                                            rep_axis=_rep_axis, cap=cap)
        with obs_trace.span(f"stkde.{strat}.dispatch"):
            # fault site dist.halo: an injected OOM here models a failed
            # strategy build (halo buffers are the PD-only allocation); the
            # api-level fallback then reroutes the query to the dr
            # baseline.
            _faults.fault_point("dist.halo")
            out = build_pd(dom, mesh, axes, n, ks, kt,
                           rep_axis=_rep_axis)(bpts, bval)
        with obs_trace.span(f"stkde.{strat}.reassemble"):
            # nan-kind injection poisons the folded halos; callers
            # validate via resilience.degrade.ensure_finite
            return _faults.poison(
                "dist.halo", _assemble_xy(out, dom, A, B, gx_loc, gy_loc))


def build_pd(dom: Domain, mesh: Mesh, axes, n: int,
             ks=km.DEFAULT_KS, kt=km.DEFAULT_KT, rep_axis=None,
             collectives: bool = True):
    """Jitted PD (owner-computes + halo exchange) over home-bucketed points.

    Input layout: (A, B, cap, 3) — or (R, A, B, cap, 3) with rep_axis for
    the hybrid/REP strategy. Dry-run lowerable with ShapeDtypeStructs.
    ``collectives=False`` skips the halo ppermute folds (and rep psum):
    the output is then the unfolded interior, a known-wrong grid that the
    benchmark's fault tests feed to its correctness check.
    """
    ax, ay = axes
    A, B = _mesh_sizes(mesh, axes)
    gx_loc, gy_loc = _device_grid_dims(dom, A, B)
    Hs = dom.Hs
    ldom = _local_domain(dom, gx_loc, gy_loc, halo=Hs)
    if rep_axis is None:
        in_specs = (P(ax, ay, None, None), P(ax, ay, None))
    else:
        in_specs = (
            P(rep_axis, ax, ay, None, None),
            P(rep_axis, ax, ay, None),
        )
    if rep_axis is not None and not collectives:
        # no rep-psum to make the output rep-invariant: return the
        # rep-stacked partial grids instead
        out_specs = P(rep_axis, ax, ay, None, None, None)
    else:
        out_specs = P(ax, ay, None, None, None)

    def f(pts_blk, val_blk):
        i = jax.lax.axis_index(ax).astype(jnp.float32)
        j = jax.lax.axis_index(ay).astype(jnp.float32)
        p = _park_invalid(
            pts_blk.reshape(-1, 3), val_blk.reshape(-1)
        )
        shift = jnp.stack(
            [
                (i * gx_loc - Hs) * dom.sres,
                (j * gy_loc - Hs) * dom.sres,
                jnp.float32(0.0),
            ]
        )
        L = _pb(p - shift, ldom, variant="sym", ks=ks, kt=kt, n_total=n)
        if not collectives:
            out = L[Hs : Hs + gx_loc, Hs : Hs + gy_loc, :][None, None]
            return out if rep_axis is None else out[None]
        # ---- fold halos: X phase (full-y slabs), then Y phase (interior-x)
        fwd_x = [(k, k + 1) for k in range(A - 1)]
        bwd_x = [(k, k - 1) for k in range(1, A)]
        from_left = jax.lax.ppermute(L[-Hs:, :, :], ax, fwd_x)
        from_right = jax.lax.ppermute(L[:Hs, :, :], ax, bwd_x)
        L = L.at[Hs : 2 * Hs].add(from_left)
        L = L.at[gx_loc : gx_loc + Hs].add(from_right)

        fwd_y = [(k, k + 1) for k in range(B - 1)]
        bwd_y = [(k, k - 1) for k in range(1, B)]
        top = L[Hs : Hs + gx_loc, -Hs:, :]
        bot = L[Hs : Hs + gx_loc, :Hs, :]
        from_bot = jax.lax.ppermute(top, ay, fwd_y)
        from_top = jax.lax.ppermute(bot, ay, bwd_y)
        interior = L[Hs : Hs + gx_loc]
        interior = interior.at[:, Hs : 2 * Hs].add(from_bot)
        interior = interior.at[:, gy_loc : gy_loc + Hs].add(from_top)
        out = interior[:, Hs : Hs + gy_loc, :]
        if rep_axis is not None:
            out = jax.lax.psum(out, rep_axis)
        return out[None, None]

    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs))


def prepare_pd_xt(
    points: np.ndarray, dom: Domain, mesh: Mesh, axes,
    cap: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Home-bucket points onto the (A, B) = (x-tile, t-tile) device grid."""
    A, B = _mesh_sizes(mesh, axes)
    pts = np.asarray(points, dtype=np.float32)
    gx_loc = math.ceil(dom.Gx / A)
    gt_loc = math.ceil(dom.Gt / B)
    b = bucketing.bucket_points_home(
        pts, dom, (gx_loc, dom.Gy, gt_loc), cap=cap
    )
    na, nt = b.ntiles[0], b.ntiles[2]
    bp, bv = _pad_tile_grid(
        b.points.reshape(na, nt, b.cap, 3),
        b.valid.reshape(na, nt, b.cap).astype(np.float32), A, B)
    return _on_mesh(mesh, axes, bp, bv)


def build_pd_xt(dom: Domain, mesh: Mesh, axes, n: int,
                ks=km.DEFAULT_KS, kt=km.DEFAULT_KT, rep_axis=None):
    """PD split over (X, T) instead of (X, Y) — §Perf STKDE iteration.

    The halo a subdomain exchanges is its boundary thickened by the
    bandwidth: splitting the *temporal* axis pays Ht-wide halos instead of
    Hs-wide ones. For long-duration instances (eBird: Gt=2435, Ht=5 vs
    Hs=30) this cuts halo traffic ~3x at identical work. Input layout:
    (A, B, cap, 3) buckets over (x-tile, t-tile).
    """
    ax, at = axes
    A, B = _mesh_sizes(mesh, axes)
    gx_loc = math.ceil(dom.Gx / A)
    gt_loc = math.ceil(dom.Gt / B)
    Hs, Ht = dom.Hs, dom.Ht
    if gx_loc < Hs or gt_loc < Ht:
        raise ValueError("PD-XT requires subdomains >= bandwidth")
    import dataclasses

    ldom = dataclasses.replace(
        dom,
        gx=(gx_loc + 2 * Hs) * dom.sres,
        gy=dom.Gy * dom.sres,
        gt=(gt_loc + 2 * Ht) * dom.tres,
    )
    if rep_axis is None:
        in_specs = (P(ax, at, None, None), P(ax, at, None))
    else:
        in_specs = (P(rep_axis, ax, at, None, None),
                    P(rep_axis, ax, at, None))
    out_specs = P(ax, at, None, None, None)

    def f(pts_blk, val_blk):
        i = jax.lax.axis_index(ax).astype(jnp.float32)
        j = jax.lax.axis_index(at).astype(jnp.float32)
        p = _park_invalid(pts_blk.reshape(-1, 3), val_blk.reshape(-1))
        shift = jnp.stack(
            [
                (i * gx_loc - Hs) * dom.sres,
                jnp.float32(0.0),
                (j * gt_loc - Ht) * dom.tres,
            ]
        )
        L = _pb(p - shift, ldom, variant="sym", ks=ks, kt=kt, n_total=n)
        # fold halos: X phase (full-t slabs), then T phase (interior-x)
        fwd_x = [(k, k + 1) for k in range(A - 1)]
        bwd_x = [(k, k - 1) for k in range(1, A)]
        L = L.at[Hs : 2 * Hs].add(
            jax.lax.ppermute(L[-Hs:], ax, fwd_x))
        L = L.at[gx_loc : gx_loc + Hs].add(
            jax.lax.ppermute(L[:Hs], ax, bwd_x))
        fwd_t = [(k, k + 1) for k in range(B - 1)]
        bwd_t = [(k, k - 1) for k in range(1, B)]
        interior = L[Hs : Hs + gx_loc]
        interior = interior.at[:, :, Ht : 2 * Ht].add(
            jax.lax.ppermute(interior[:, :, -Ht:], at, fwd_t))
        interior = interior.at[:, :, gt_loc : gt_loc + Ht].add(
            jax.lax.ppermute(interior[:, :, :Ht], at, bwd_t))
        out = interior[:, :, Ht : Ht + gt_loc]
        if rep_axis is not None:
            out = jax.lax.psum(out, rep_axis)
        return out[None, None]

    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs))


def stkde_pd_xt(
    points: np.ndarray,
    dom: Domain,
    mesh: Mesh,
    axes: Tuple[str, str] = ("data", "model"),
    cap: Optional[int] = None,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    n_total: Optional[int] = None,
) -> jnp.ndarray:
    """PD with an (X, T) device grid (small temporal halos)."""
    ax, at = axes
    A, B = _mesh_sizes(mesh, axes)
    pts = np.asarray(points, dtype=np.float32)
    n = int(n_total) if n_total is not None else len(pts)
    gx_loc = math.ceil(dom.Gx / A)
    gt_loc = math.ceil(dom.Gt / B)
    with obs_trace.span("stkde.pd_xt", n=n, mesh=str(dict(mesh.shape))):
        with obs_trace.span("stkde.pd_xt.bucket"):
            bpts, bval = prepare_pd_xt(pts, dom, mesh, axes, cap=cap)
        with obs_trace.span("stkde.pd_xt.dispatch"):
            out = build_pd_xt(dom, mesh, axes, n, ks, kt)(bpts, bval)
        with obs_trace.span("stkde.pd_xt.reassemble"):
            out = out.reshape(A, B, gx_loc, dom.Gy, gt_loc)
            out = out.transpose(0, 2, 3, 1, 4).reshape(
                A * gx_loc, dom.Gy, B * gt_loc)
            return out[: dom.Gx, :, : dom.Gt]


def prepare_pd_xyt(
    points: np.ndarray, dom: Domain, mesh: Mesh, axes,
    cap: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Home-bucket points onto the (A, B, C) = (x, y, t) device grid."""
    A, B, C = _mesh_sizes(mesh, axes)
    pts = np.asarray(points, dtype=np.float32)
    gx_loc = math.ceil(dom.Gx / A)
    gy_loc = math.ceil(dom.Gy / B)
    gt_loc = math.ceil(dom.Gt / C)
    b = bucketing.bucket_points_home(
        pts, dom, (gx_loc, gy_loc, gt_loc), cap=cap
    )
    na, nb, nt = b.ntiles
    pp = np.full((A, B, C, b.cap, 3), PARK, dtype=np.float32)
    vv = np.zeros((A, B, C, b.cap), dtype=np.float32)
    pp[:na, :nb, :nt] = b.points
    vv[:na, :nb, :nt] = b.valid.astype(np.float32)
    return _on_mesh(mesh, axes, pp, vv)


def build_pd_xyt(dom: Domain, mesh: Mesh, axes, n: int,
                 ks=km.DEFAULT_KS, kt=km.DEFAULT_KT):
    """Full 3-D PD decomposition (the paper's A×B×C) for multi-pod meshes.

    Splits (X, Y, T) over three mesh axes — e.g. pod×data×model = 2×16×16
    — with halo folds in all three directions (Hs, Hs, Ht wide). On the
    multi-pod mesh this keeps each subdomain 512× smaller than the grid
    while halo traffic stays proportional to subdomain surface; the
    cross-pod (DCN) direction is X, which exchanges only two
    Hs-thick slabs per build.
    """
    ax, ay, at = axes
    A, B, C = _mesh_sizes(mesh, axes)
    gx_loc = math.ceil(dom.Gx / A)
    gy_loc = math.ceil(dom.Gy / B)
    gt_loc = math.ceil(dom.Gt / C)
    Hs, Ht = dom.Hs, dom.Ht
    if gx_loc < Hs or gy_loc < Hs or gt_loc < Ht:
        raise ValueError("PD-XYT requires subdomains >= bandwidth")
    import dataclasses

    ldom = dataclasses.replace(
        dom,
        gx=(gx_loc + 2 * Hs) * dom.sres,
        gy=(gy_loc + 2 * Hs) * dom.sres,
        gt=(gt_loc + 2 * Ht) * dom.tres,
    )
    in_specs = (P(ax, ay, at, None, None), P(ax, ay, at, None))
    out_specs = P(ax, ay, at, None, None, None)

    def f(pts_blk, val_blk):
        i = jax.lax.axis_index(ax).astype(jnp.float32)
        j = jax.lax.axis_index(ay).astype(jnp.float32)
        k = jax.lax.axis_index(at).astype(jnp.float32)
        p = _park_invalid(pts_blk.reshape(-1, 3), val_blk.reshape(-1))
        shift = jnp.stack(
            [
                (i * gx_loc - Hs) * dom.sres,
                (j * gy_loc - Hs) * dom.sres,
                (k * gt_loc - Ht) * dom.tres,
            ]
        )
        L = _pb(p - shift, ldom, variant="sym", ks=ks, kt=kt, n_total=n)
        # X phase (full-(y,t) slabs) -> Y phase (interior-x) -> T phase
        fwd = lambda nn: [(q, q + 1) for q in range(nn - 1)]
        bwd = lambda nn: [(q, q - 1) for q in range(1, nn)]
        L = L.at[Hs : 2 * Hs].add(jax.lax.ppermute(L[-Hs:], ax, fwd(A)))
        L = L.at[gx_loc : gx_loc + Hs].add(
            jax.lax.ppermute(L[:Hs], ax, bwd(A)))
        ix = L[Hs : Hs + gx_loc]
        ix = ix.at[:, Hs : 2 * Hs].add(
            jax.lax.ppermute(ix[:, -Hs:], ay, fwd(B)))
        ix = ix.at[:, gy_loc : gy_loc + Hs].add(
            jax.lax.ppermute(ix[:, :Hs], ay, bwd(B)))
        iy = ix[:, Hs : Hs + gy_loc]
        iy = iy.at[:, :, Ht : 2 * Ht].add(
            jax.lax.ppermute(iy[:, :, -Ht:], at, fwd(C)))
        iy = iy.at[:, :, gt_loc : gt_loc + Ht].add(
            jax.lax.ppermute(iy[:, :, :Ht], at, bwd(C)))
        out = iy[:, :, Ht : Ht + gt_loc]
        return out[None, None, None]

    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs))


def stkde_pd_xyt(
    points: np.ndarray,
    dom: Domain,
    mesh: Mesh,
    axes: Tuple[str, str, str] = ("pod", "data", "model"),
    cap: Optional[int] = None,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    n_total: Optional[int] = None,
) -> jnp.ndarray:
    """Paper-style 3-D decomposition across a three-axis (multi-pod) mesh."""
    A, B, C = _mesh_sizes(mesh, axes)
    pts = np.asarray(points, dtype=np.float32)
    n = int(n_total) if n_total is not None else len(pts)
    gx_loc = math.ceil(dom.Gx / A)
    gy_loc = math.ceil(dom.Gy / B)
    gt_loc = math.ceil(dom.Gt / C)
    with obs_trace.span("stkde.pd_xyt", n=n, mesh=str(dict(mesh.shape))):
        with obs_trace.span("stkde.pd_xyt.bucket"):
            bpts, bval = prepare_pd_xyt(pts, dom, mesh, axes, cap=cap)
        with obs_trace.span("stkde.pd_xyt.dispatch"):
            out = build_pd_xyt(dom, mesh, axes, n, ks, kt)(bpts, bval)
        with obs_trace.span("stkde.pd_xyt.reassemble"):
            out = out.reshape(A, B, C, gx_loc, gy_loc, gt_loc)
            out = out.transpose(0, 3, 1, 4, 2, 5).reshape(
                A * gx_loc, B * gy_loc, C * gt_loc)
            return out[: dom.Gx, : dom.Gy, : dom.Gt]


# ------------------------------------------------------------------ hybrid
def prepare_hybrid(
    points: np.ndarray, dom: Domain, mesh: Mesh, axes,
    rep_axis: str = "pod", cap: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Home-bucket points, then deal each bucket round-robin over ``rep``.

    Returns (R, A, B, cap_r, 3) points and (R, A, B, cap_r) valid masks —
    the input layout ``build_pd(..., rep_axis=...)`` expects.
    """
    A, B = _mesh_sizes(mesh, axes)
    R = mesh.shape[rep_axis]
    pts = np.asarray(points, dtype=np.float32)
    gx_loc, gy_loc = _device_grid_dims(dom, A, B)
    b = bucketing.bucket_points_home(
        pts, dom, (gx_loc, gy_loc, dom.Gt), cap=cap
    )
    na, nb = b.ntiles[0], b.ntiles[1]
    src, val = _pad_tile_grid(
        b.points.reshape(na, nb, b.cap, 3),
        b.valid.reshape(na, nb, b.cap).astype(np.float32), A, B)
    # deal bucket contents over R replicas
    cap_r = bucketing.round_up(max(1, -(-b.cap // R)), 8)
    dpts = np.full((R, A, B, cap_r, 3), PARK, dtype=np.float32)
    dval = np.zeros((R, A, B, cap_r), dtype=np.float32)
    pos = np.arange(b.cap)
    r_of = pos % R
    p_of = pos // R
    dpts[r_of, :, :, p_of] = np.transpose(src, (2, 0, 1, 3))
    dval[r_of, :, :, p_of] = np.transpose(val, (2, 0, 1)).astype(np.float32)
    return _on_mesh(mesh, (rep_axis,) + tuple(axes), dpts, dval)


def stkde_hybrid(
    points: np.ndarray,
    dom: Domain,
    mesh: Mesh,
    axes: Tuple[str, str] = ("data", "model"),
    rep_axis: str = "pod",
    cap: Optional[int] = None,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    n_total: Optional[int] = None,
) -> jnp.ndarray:
    """PD over the worker grid × DR over the ``rep`` axis (PB-SYM-PD-REP).

    Every bucket's points are dealt round-robin over the rep axis — the
    moldable-task replication of the paper expressed as a mesh dimension.
    """
    return stkde_pd(points, dom, mesh, axes, cap=cap, ks=ks, kt=kt,
                    n_total=n_total, _rep_axis=rep_axis)


# ------------------------------------------------------------------ DD-LPT
def prepare_dd_lpt(
    points: np.ndarray, dom: Domain, mesh: Mesh, axes,
    tile: Optional[Tuple[int, int, int]] = None,
    cap: Optional[int] = None,
):
    """Fine-tile bucket + LPT placement for DD-LPT.

    Returns ``((dpts, dval, dpos), ctx)`` where the first element is the
    argument tuple for the jitted builder and ``ctx`` carries the
    point-dependent static parameters (``tile``, ``k``, ``cap``,
    ``ntiles``) that ``build_dd_lpt`` needs to compile.
    """
    A, B = _mesh_sizes(mesh, axes)
    Ptot = A * B
    pts = np.asarray(points, dtype=np.float32)
    if tile is None:
        tile = bucketing.default_tile(dom)
    bx, by, bt = tile
    b = bucketing.bucket_points_overlap(pts, dom, tile, cap=cap)
    ntx, nty, ntt = b.ntiles
    loads = b.counts.reshape(-1).astype(np.float64)
    assign = partition.lpt_assign(loads, Ptot)
    k = max(len(t) for t in assign.tiles_of_device)

    capn = b.cap
    dpts = np.full((Ptot, k, capn, 3), PARK, dtype=np.float32)
    dval = np.zeros((Ptot, k, capn), dtype=np.float32)
    dpos = np.zeros((Ptot, k, 3), dtype=np.int32)
    flat_pts = b.points.reshape(-1, capn, 3)
    flat_val = b.valid.reshape(-1, capn)
    for p, tiles in enumerate(assign.tiles_of_device):
        for s, t in enumerate(tiles):
            ti, tj, tk = np.unravel_index(t, (ntx, nty, ntt))
            dpts[p, s] = flat_pts[t]
            dval[p, s] = flat_val[t]
            dpos[p, s] = (ti * bx, tj * by, tk * bt)
    args = _on_mesh(mesh, (tuple(axes),), dpts, dval, dpos)
    ctx = {"tile": tile, "k": k, "cap": capn, "ntiles": b.ntiles}
    return args, ctx


def build_dd_lpt(dom: Domain, mesh: Mesh, axes, n: int,
                 tile: Tuple[int, int, int], k: int, cap: int,
                 ntiles: Tuple[int, int, int],
                 ks=km.DEFAULT_KS, kt=km.DEFAULT_KT):
    """Jitted DD-LPT over LPT-placed tile soup (dry-run lowerable).

    Static parameters (``tile``, ``k``, ``cap``, ``ntiles``) come from
    ``prepare_dd_lpt``'s ctx.
    """
    ax, ay = axes
    bx, by, bt = tile
    ntx, nty, ntt = ntiles
    Gxp, Gyp, Gtp = ntx * bx, nty * by, ntt * bt
    norm = km.normalization(n, dom.hs, dom.ht)

    def one_tile(pts_t, val_t, pos_t):
        """Separable PB-SYM contraction for one (bx, by, bt) tile."""
        xc = dom.ox + (pos_t[0].astype(jnp.float32)
                       + jnp.arange(bx, dtype=jnp.float32) + 0.5) * dom.sres
        yc = dom.oy + (pos_t[1].astype(jnp.float32)
                       + jnp.arange(by, dtype=jnp.float32) + 0.5) * dom.sres
        tc = dom.ot + (pos_t[2].astype(jnp.float32)
                       + jnp.arange(bt, dtype=jnp.float32) + 0.5) * dom.tres
        u = (xc[None, :] - pts_t[:, 0:1]) / dom.hs
        v = (yc[None, :] - pts_t[:, 1:2]) / dom.hs
        w = (tc[None, :] - pts_t[:, 2:3]) / dom.ht
        Ks = ks(u[:, :, None], v[:, None, :]) * norm
        Kt = kt(w) * val_t[:, None]
        return jnp.einsum("pxy,pt->xyt", Ks, Kt,
                          precision=jax.lax.Precision.HIGHEST)

    def f(pts_blk, val_blk, pos_blk):  # (1,k,cap,3), (1,k,cap), (1,k,3)
        tiles = jax.vmap(one_tile)(pts_blk[0], val_blk[0], pos_blk[0])

        def place(s, g):
            return jax.lax.dynamic_update_slice(
                g,
                jax.lax.dynamic_slice(
                    g,
                    (pos_blk[0, s, 0], pos_blk[0, s, 1], pos_blk[0, s, 2]),
                    (bx, by, bt),
                )
                + tiles[s],
                (pos_blk[0, s, 0], pos_blk[0, s, 1], pos_blk[0, s, 2]),
            )

        g0 = jax.lax.pcast(
            jnp.zeros((Gxp, Gyp, Gtp), jnp.float32), (ax, ay), to="varying"
        )
        g = jax.lax.fori_loop(0, k, place, g0)
        return jax.lax.psum(g, (ax, ay))

    return jax.jit(shard_map(
        f,
        mesh=mesh,
        in_specs=(
            P((ax, ay), None, None, None),
            P((ax, ay), None, None),
            P((ax, ay), None, None),
        ),
        out_specs=P(None, None, None),
    ))


def stkde_dd_lpt(
    points: np.ndarray,
    dom: Domain,
    mesh: Mesh,
    axes: Tuple[str, str] = ("data", "model"),
    tile: Optional[Tuple[int, int, int]] = None,
    cap: Optional[int] = None,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    n_total: Optional[int] = None,
) -> jnp.ndarray:
    """Fine-tile DD with LPT load-aware placement (PD-SCHED as placement).

    Each device receives the k tiles LPT assigned to it (capacity-padded
    "tile soup"), computes each tile's density with the separable contraction,
    scatters them into a device-local grid, and the grids are summed — tiles
    are disjoint, so the psum is pure assembly, not numerical reduction.
    """
    pts = np.asarray(points, dtype=np.float32)
    n = int(n_total) if n_total is not None else len(pts)
    with obs_trace.span("stkde.dd_lpt", n=n, mesh=str(dict(mesh.shape))):
        with obs_trace.span("stkde.dd_lpt.bucket"):
            args, ctx = prepare_dd_lpt(pts, dom, mesh, axes, tile=tile,
                                       cap=cap)
        with obs_trace.span("stkde.dd_lpt.dispatch"):
            out = build_dd_lpt(
                dom, mesh, axes, n, ctx["tile"], ctx["k"], ctx["cap"],
                ctx["ntiles"], ks, kt,
            )(*args)
        with obs_trace.span("stkde.dd_lpt.reassemble"):
            return out[: dom.Gx, : dom.Gy, : dom.Gt]


STRATEGIES = {
    "dr": stkde_dr,
    "dd": stkde_dd,
    "pd": stkde_pd,
    "pd_xt": stkde_pd_xt,
    "pd_xyt": stkde_pd_xyt,
    "dd_lpt": stkde_dd_lpt,
    "hybrid": stkde_hybrid,
}


# -------------------------------------------------------------- chunked
def execute_chunk(
    points: np.ndarray,
    dom: Domain,
    mesh: Mesh,
    strategy: str,
    axes: Tuple[str, ...] = ("data", "model"),
    rep_axis: Optional[str] = None,
    cap: Optional[int] = None,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    n_total: Optional[int] = None,
) -> jnp.ndarray:
    """One chunk of a chunked run on the current mesh (normalized by the
    *global* ``n_total``).

    The ``dist.device`` fault site models a device dying mid-chunk: an
    injected oom/drop here surfaces as a non-transient ``DeviceLostError``
    so the chunked executor (``core.api.stkde_chunked``) re-plans the
    remaining chunks onto a shrunken mesh instead of retrying a dead one.
    """
    from repro.resilience.errors import DeviceLostError, FaultInjectedError

    shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    try:
        _faults.fault_point("dist.device")
    except FaultInjectedError as e:
        raise DeviceLostError("dist.device", mesh_shape=shape) from e
    fn = STRATEGIES[strategy]
    kw = dict(axes=axes, ks=ks, kt=kt, n_total=n_total)
    if strategy == "hybrid":
        kw["rep_axis"] = rep_axis or "pod"
    if strategy == "pd_xyt" and len(axes) == 2:
        # 3-D split needs a third mesh axis: the rep axis becomes the X cut
        kw["axes"] = (rep_axis or "pod",) + tuple(axes)
    if cap is not None and strategy in ("dd", "pd", "pd_xt", "pd_xyt"):
        # fixed bucket capacity keeps the jitted shapes identical across
        # chunks (one compile per (strategy, mesh), not per chunk)
        kw["cap"] = cap
    return fn(points, dom, mesh, **kw)

"""Parametric strategy planner — the paper's §6.5 future work, implemented.

"What we need to do is to develop a parametric model for the problem that
 will take into account memory availability, cost of memory initialization,
 expected cost of computing the kernel density. Using that model finding the
 best execution strategy becomes a combinatorial problem."

Given an instance (grid, bandwidths, point loads) and a device mesh, this
module prices every strategy with a three-term model (the same decomposition
the roofline analysis uses):

    time = init(HBM memset)  +  point-work(FLOPs, x imbalance)  +  collectives

and returns the argmin. On one device ``choose_single`` prices the XLA
scatter against the Pallas tile kernel the same way. Hardware constants
default to TPU v5e; ``default_hw()`` picks them by the device JAX runs on.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import numpy as np

from .geometry import Domain
from . import bucketing
from repro.distributed import partition


@dataclasses.dataclass(frozen=True)
class Hardware:
    peak_flops: float = 197e12   # bf16 (MXU); fp32 VPU path derated below
    hbm_bw: float = 819e9        # bytes/s
    ici_bw: float = 50e9         # bytes/s/link
    hbm_bytes: float = 16e9      # per chip
    vpu_derate: float = 0.04     # scatter path ~ VPU: few % of MXU peak
    mxu_derate: float = 0.5      # tile-GEMM path: realistic MXU fraction
    # single-device paths (``choose_single``): seconds per unit of work, a
    # least-squares fit to second calls of both paths, each forced, on
    # eleven Table 2 instances on one TPU v5e (PERF.md §6)
    pb_update_s: float = 1.04e-8     # scatter: per cylinder update
    tile_copy_s: float = 1.56e-6     # tile kernel: per overlap copy
    tile_voxel_s: float = 2.6e-9     # tile kernel: per padded voxel


V5E = Hardware()

# Peaks by ``device_kind``. v5e: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip).
PEAKS: Dict[str, Hardware] = {"TPU v5 lite": V5E}


def default_hw() -> Hardware:
    """The Hardware model of JAX's first device: the ``PEAKS`` entry of its
    ``device_kind``. On the CPU it is ``V5E``, the chip the planner plans
    for, so a CPU run plans exactly as the v5e would. An accelerator kind
    with no entry is an error, not a default."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return V5E
    try:
        return PEAKS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware peaks for device_kind {dev.device_kind!r}; "
            "add them to repro.core.plan.PEAKS") from None


def _point_work_flops(dom: Domain, n_eff: float) -> float:
    """PB-SYM flops: disk eval + bar eval + cylinder outer-product FMA."""
    disk = (2 * dom.Hs + 1) ** 2
    bar = 2 * dom.Ht + 1
    return n_eff * (disk * 10.0 + bar * 5.0 + disk * bar * 2.0)


def estimate(
    dom: Domain,
    n: int,
    mesh_shape: Tuple[int, ...],
    loads: Optional[np.ndarray] = None,
    hw: Hardware = V5E,
) -> Dict[str, Dict[str, float]]:
    """Per-strategy cost breakdown in seconds. mesh_shape=(A, B) or (R, A, B)."""
    if len(mesh_shape) == 3:
        R, A, B = mesh_shape
    else:
        R, (A, B) = 1, mesh_shape
    P = R * A * B
    Gb = dom.grid_voxels * 4.0                      # grid bytes
    gx_loc = math.ceil(dom.Gx / A)
    gy_loc = math.ceil(dom.Gy / B)
    sub_b = gx_loc * gy_loc * dom.Gt * 4.0
    halo_b = 2 * (gx_loc + gy_loc + 2 * dom.Hs) * dom.Hs * dom.Gt * 4.0
    # Two compute paths with very different efficiency: the scatter-based
    # PB-SYM strategies (dr/dd/pd/pd_xt/pd_xyt/hybrid) run at the VPU
    # rate, while dd_lpt's separable tile contraction is a GEMM (MXU)
    # workload.
    rate_scatter = hw.peak_flops * hw.vpu_derate
    rate_tile = hw.peak_flops * hw.mxu_derate

    # overlap replication factor (cut cylinders) for DD-style strategies
    tiles_per_dim_x = max(1.0, gx_loc / (2 * dom.Hs + 1))
    rep_dd = (1 + 1 / tiles_per_dim_x) * (
        1 + 1 / max(1.0, gy_loc / (2 * dom.Hs + 1))
    )

    # imbalance: measured from per-bucket loads when available
    if loads is not None:
        stats_ab = partition.imbalance_stats(loads, A * B)
        imb_block = stats_ab["block_imbalance"]
        imb_lpt = stats_ab["lpt_imbalance"]
    else:
        imb_block, imb_lpt = 2.5, 1.05              # pessimistic defaults

    w = _point_work_flops(dom, float(n))
    out: Dict[str, Dict[str, float]] = {}

    def entry(init_b, flops, imb, comm_b, mem_b, rate=rate_scatter):
        compute_s = flops * imb / (P * rate)
        return {
            "init_s": init_b / hw.hbm_bw,
            "compute_s": compute_s,
            "comm_s": comm_b / hw.ici_bw,
            "mem_per_dev_gb": mem_b / 1e9,
            "feasible": float(mem_b < hw.hbm_bytes),
            "total_s": init_b / hw.hbm_bw + compute_s + comm_b / hw.ici_bw,
        }

    # DR: full grid per device; ring all-reduce ~ 2*Gb*(P-1)/P per device
    out["dr"] = entry(Gb, w, 1.0, 2 * Gb * (P - 1) / P, 2 * Gb)
    # DD: subgrid per device; replicated points; no comm
    out["dd"] = entry(sub_b, w * rep_dd, imb_block, 0.0, sub_b)
    # PD: halo-extended subgrid; halo exchange; work-efficient
    pd_feasible = gx_loc >= dom.Hs and gy_loc >= dom.Hs
    out["pd"] = entry(
        (gx_loc + 2 * dom.Hs) * (gy_loc + 2 * dom.Hs) * dom.Gt * 4.0,
        w,
        imb_block,
        halo_b,
        sub_b * 2,
    )
    out["pd"]["feasible"] *= float(pd_feasible)
    # PD-XT: split (X, T) — temporal halos are Ht-wide (cheap for
    # long-duration instances); Y unsharded.
    gt_loc = math.ceil(dom.Gt / B)
    halo_xt = 2 * (dom.Hs * dom.Gy * (gt_loc + 2 * dom.Ht)
                   + dom.Ht * gx_loc * dom.Gy) * 4.0
    out["pd_xt"] = entry(
        (gx_loc + 2 * dom.Hs) * dom.Gy * (gt_loc + 2 * dom.Ht) * 4.0,
        w,
        imb_block,
        halo_xt,
        gx_loc * dom.Gy * gt_loc * 4.0 * 2,
    )
    out["pd_xt"]["feasible"] *= float(
        gx_loc >= dom.Hs and gt_loc >= dom.Ht)
    # PD-XYT: full 3-D split — a 3-tuple mesh_shape is read as the
    # (X, Y, T) device grid for this entry (the leading axis splits X
    # instead of replicating). On a 2-D mesh there is no T axis to
    # split, so the strategy is priced like pd but marked infeasible.
    if len(mesh_shape) == 3:
        X, Y, T = mesh_shape
        gx3 = math.ceil(dom.Gx / X)
        gy3 = math.ceil(dom.Gy / Y)
        gt3 = math.ceil(dom.Gt / T)
        halo_xyt = 2 * (
            dom.Hs * gy3 * gt3 + dom.Hs * gx3 * gt3 + dom.Ht * gx3 * gy3
        ) * 4.0
        out["pd_xyt"] = entry(
            (gx3 + 2 * dom.Hs) * (gy3 + 2 * dom.Hs)
            * (gt3 + 2 * dom.Ht) * 4.0,
            w,
            imb_block,
            halo_xyt,
            gx3 * gy3 * gt3 * 4.0 * 2,
        )
        out["pd_xyt"]["feasible"] *= float(
            gx3 >= dom.Hs and gy3 >= dom.Hs and gt3 >= dom.Ht)
    else:
        out["pd_xyt"] = dict(out["pd"])
        out["pd_xyt"]["feasible"] = 0.0
    # DD-LPT: full grid per device (tile soup assembly via psum); the
    # only strategy on the tile-GEMM compute path
    out["dd_lpt"] = entry(
        Gb, w * rep_dd, imb_lpt, 2 * Gb * (P - 1) / P, 2 * Gb,
        rate=rate_tile,
    )
    # hybrid (R-way REP over PD): psum of subgrids over R + halo
    out["hybrid"] = entry(
        (gx_loc + 2 * dom.Hs) * (gy_loc + 2 * dom.Hs) * dom.Gt * 4.0,
        w,
        max(1.0, imb_block / R),
        halo_b + 2 * sub_b * (R - 1) / R,
        sub_b * 2,
    )
    out["hybrid"]["feasible"] *= float(pd_feasible)
    return out


def choose(
    dom: Domain,
    n: int,
    mesh_shape: Tuple[int, ...],
    loads: Optional[np.ndarray] = None,
    hw: Hardware = V5E,
) -> Tuple[str, Dict[str, Dict[str, float]]]:
    """Best feasible strategy and the full cost table."""
    table = estimate(dom, n, mesh_shape, loads, hw)
    feas = {k: v for k, v in table.items() if v["feasible"] > 0}
    pick = min(feas or table, key=lambda k: (feas or table)[k]["total_s"])
    return pick, table


def choose_single(dom: Domain, n: int, hw: Optional[Hardware] = None
                  ) -> Tuple[str, Dict[str, float]]:
    """The one-device path for ``n`` points on ``dom``, and each path's
    price in seconds on ``hw`` (default ``default_hw()``).

    ``pb``, the XLA scatter, costs one unit per cylinder update,
    ``n (2Hs+1)^2 (2Ht+1)``. ``tiled``, the Pallas tile kernel under
    ``kernels.ops.default_tile``, costs one unit per point copy that overlap
    bucketing makes (each point lands in every tile its cylinder's box
    meets, on average ``1 + 2H/b`` tiles a dimension) and one per voxel of
    the padded grid. Neither price looks at where the points lie. The
    cheaper path wins; off the TPU it is always "pb", since the tile kernel
    compiles for the TPU only.
    """
    import jax
    from repro.kernels.ops import default_tile

    hw = default_hw() if hw is None else hw
    tile = default_tile(dom)
    nt = bucketing.num_tiles(dom, tile)
    copies = float(n)
    for b, k, h in zip(tile, nt, (dom.Hs, dom.Hs, dom.Ht)):
        copies *= min(1.0 + 2.0 * h / b, k)
    padded = math.prod(nt) * math.prod(tile)
    updates = float(n) * (2 * dom.Hs + 1) ** 2 * (2 * dom.Ht + 1)
    prices = {"pb": updates * hw.pb_update_s,
              "tiled": copies * hw.tile_copy_s + padded * hw.tile_voxel_s}
    if jax.default_backend() != "tpu":
        return "pb", prices
    return min(prices, key=prices.get), prices

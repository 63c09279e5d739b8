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
import json
import math
import os
from typing import Dict, Optional, Sequence, Tuple

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

# Rough single-host CPU constants for reconciliation smoke runs (8 fake XLA
# host devices share one socket, so per-"device" rates are fractions of the
# socket). HOST_SEED is the uncalibrated starting point; HOST below folds in
# the measured reconcile rows.
HOST_SEED = Hardware(
    peak_flops=5e10,     # per fake device, fp32 vector path
    hbm_bw=4e9,          # DRAM bandwidth share per fake device
    ici_bw=4e9,          # "collective" = memcpy through shared memory
    hbm_bytes=4e9,
    vpu_derate=1.0,      # scatter path on CPU is the same ALUs
    mxu_derate=1.0,
    pb_update_s=1.8e-8,  # XLA:CPU scatter, one process (PERF.md §6)
    tile_copy_s=math.inf,    # no compiled tile kernel off the TPU
    tile_voxel_s=math.inf,
)

# Calibrated against results/bench/reconcile.json (mesh 2x2x2, n=8000, all
# seven probed strategies): XLA:CPU's scatter path dispatches per point,
# nowhere near vector peak — peak_flops carries the geo-mean-fitted scatter
# rate (dr/dd/pd/pd_xt/pd_xyt/hybrid compute rel-err lands within ~2x).
# dd_lpt's separable tile contraction is a GEMM and runs ~15x faster than
# the scatter strategies on the same cores, so its rate is carried
# separately in mxu_derate (see estimate()'s rate_tile). Memory-bandwidth
# (init) terms were already within ~2x and are left at their seed values,
# as is ici_bw (the collective probes measure ~ms-scale comm on shared
# memory, so a bandwidth "fit" is unidentifiable from these rows and would
# distort choose()).
HOST = dataclasses.replace(HOST_SEED, peak_flops=3.0e6, mxu_derate=15.5)


def probed_strategies() -> Tuple[str, ...]:
    """Strategy names with a phase-probe spec (``obs.reconcile.PROBED``).

    Single source of truth for which rows calibration may trust — derived
    from the probe registry so the two can never drift.
    """
    from repro.obs import reconcile

    return tuple(reconcile.PROBED)


# strategies whose compute runs on the tile-GEMM (einsum/MXU) path; every
# other strategy is on the scatter (VPU) path — see estimate()
TILE_PATH = ("dd_lpt",)


def calibrate_host(rows, base: Hardware = HOST_SEED,
                   strategies: Optional[Sequence[str]] = None) -> Hardware:
    """Re-fit the host compute rates from reconcile rows.

    ``rows`` is the ``rows`` list of a ``obs.reconcile`` report (or a path
    to one): entries with ``term == "compute_s"`` and positive
    predicted/measured values contribute ``measured / predicted`` ratios.
    ``base.peak_flops`` (the Hardware that *produced* those predictions)
    is divided by the geometric mean of the scatter-path strategies'
    ratios; ``base.mxu_derate`` is re-fitted from the ``TILE_PATH``
    strategies' ratios so the tile-GEMM rate tracks its own measurement.
    Terms other than compute are left untouched — see the HOST comment
    above.

    ``strategies`` limits which rows contribute; it defaults to the probe
    registry keys (``obs.reconcile.PROBED``) so rows from unknown or
    retired strategies in an old report can't skew the fit.
    """
    if isinstance(rows, (str, os.PathLike)):
        with open(rows) as f:
            rows = json.load(f)
    if isinstance(rows, dict):
        rows = rows.get("rows", [])
    if rows and isinstance(rows[0], dict) and "rows" in rows[0]:
        # a reconcile.json file: list of per-run reports, each with rows
        rows = [r for rep in rows for r in rep.get("rows", [])]
    allowed = set(probed_strategies() if strategies is None else strategies)

    def geomean_ratio(names):
        ratios = [
            r["measured_s"] / r["predicted_s"]
            for r in rows
            if r.get("term") == "compute_s"
            and r.get("strategy") in names
            and r.get("predicted_s", 0) > 0 and r.get("measured_s", 0) > 0
        ]
        if not ratios:
            return None
        return math.exp(sum(math.log(x) for x in ratios) / len(ratios))

    g_scatter = geomean_ratio(allowed - set(TILE_PATH))
    g_tile = geomean_ratio(allowed & set(TILE_PATH))
    out = base
    if g_scatter is not None:
        out = dataclasses.replace(out, peak_flops=base.peak_flops / g_scatter)
    if g_tile is not None:
        # tile rate = peak_flops * mxu_derate must shrink by g_tile; the
        # peak_flops change above is compensated inside the derate
        scale = g_scatter if g_scatter is not None else 1.0
        out = dataclasses.replace(
            out, mxu_derate=base.mxu_derate * scale / g_tile)
    return out


# Peaks by ``device_kind``. v5e: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip).
PEAKS: Dict[str, Hardware] = {"TPU v5 lite": V5E}


def default_hw() -> Hardware:
    """The Hardware model of JAX's first device: HOST on the CPU, the
    ``PEAKS`` entry of its ``device_kind`` otherwise. A device kind with no
    entry is an error, not a default."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return HOST
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
    use_mxu: bool = True,
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
    # workload. Pricing them with one shared rate hid a >10x compute
    # misprediction for dd_lpt in the reconcile rows.
    rate_scatter = hw.peak_flops * hw.vpu_derate
    rate_tile = hw.peak_flops * (
        hw.mxu_derate if use_mxu else hw.vpu_derate
    )

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

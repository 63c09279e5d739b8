"""Pallas tile-kernel microbenchmark: scatter-PB vs tile oracle vs the
kernel's structural cost model.

The Pallas kernel itself does not run here: it compiles only for the TPU,
where ``chip_smoke.py`` runs it. What this section times is (a) the
*scatter* path vs the *dense tile* oracle (``kernels.ref``, the same
contraction in plain jnp, over the same panel-packed points) on the local
backend — the structural advantage that motivates the TPU kernel — and (b)
the kernel's analytic MXU utilisation per tile and panel size (the numbers
that justify the default_tile choice in kernels/ops.py).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro.core import Domain, pb, clustered_events, bucketing
from repro.kernels import stkde_tiled, default_tile
from repro.obs import timeit


def tile_gemm_stats(dom: Domain, tile, panel: int) -> Dict:
    """Structural analysis of one grid step's GEMM (V_s x P) @ (P x V_t),
    P the points of one panel."""
    bx, by, bt = tile
    m, k, n = bx * by, panel, bt
    flops = 2 * m * k * n
    # bytes: Ks panel + Kt panel + accumulator (VMEM-resident)
    vmem = 4 * (k * m + k * n + m * n)
    # MXU alignment: fraction of 128x128 systolic tiles that are full
    util_m = m / (-(-m // 128) * 128)
    util_n = n / (-(-n // 128) * 128)
    return {
        "tile": f"{bx}x{by}x{bt}", "gemm": f"({m}x{k})@({k}x{n})",
        "flops_per_tile": flops, "vmem_bytes": vmem,
        "mxu_fill": round(util_m * util_n, 3),
        "arith_intensity": round(flops / vmem, 1),
    }


def run(quick=False) -> List[Dict]:
    dom = Domain(gx=96.0, gy=96.0, gt=32.0, sres=1.0, tres=1.0,
                 hs=4.0, ht=2.0)
    pts = clustered_events(3000 if quick else 10_000, dom, seed=0)
    rows = []
    t_scatter = timeit(lambda: pb(pts, dom), name="kernel.scatter_pb").best
    t_tiled_ref = timeit(lambda: stkde_tiled(pts, dom, use_ref=True),
                         name="kernel.tiled_dense").best
    rows.append({
        "bench": "scatter_vs_tiled(cpu)",
        "scatter_pb_s": round(t_scatter, 4),
        "tiled_dense_s": round(t_tiled_ref, 4),
        "note": "dense tile path = structure the TPU kernel exploits",
    })
    print(f"  scatter={t_scatter:.4f}s tiled(dense jnp)={t_tiled_ref:.4f}s")
    for tile, panel in (((8, 8, 8), 128), ((16, 16, 8), 256),
                        ((32, 32, 16), 512), ((32, 32, 8), 1024)):
        s = tile_gemm_stats(dom, tile, panel)
        rows.append({"bench": "tile_gemm_structure", **s})
        print(f"  tile {s['tile']}: {s['gemm']} MXU fill {s['mxu_fill']} "
              f"AI {s['arith_intensity']}")
    return rows

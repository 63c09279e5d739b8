"""Quickstart: STKDE on a synthetic epidemic, strategy auto-selection.

    PYTHONPATH=src python examples/quickstart.py

Builds a Dengue-like clustered space-time dataset, computes the density
volume with the single-device PB-SYM path and the Pallas tile kernel,
verifies they agree, and prints what the parametric planner (paper §6.5,
implemented in core/plan.py) would choose on a production mesh.
"""
import numpy as np
import jax

from repro.core import Domain, pb, clustered_events, bucketing
from repro.core.api import stkde
from repro.core.plan import choose
from repro.compile_cache import enable_compile_cache
from repro.kernels import stkde_tiled


def main():
    enable_compile_cache()
    # a city-scale domain: 30km x 24km at 100m resolution, 120 days
    dom = Domain(gx=30_000, gy=24_000, gt=120, sres=100, tres=1,
                 hs=500, ht=7)
    print(f"domain: {dom.describe()}")
    pts = clustered_events(20_000, dom, seed=42)

    grid = np.asarray(stkde(pts, dom))                 # scatter PB-SYM
    # Pallas tile kernel: compiled on the TPU, interpreted (slowly) on a CPU
    mode = "compiled" if jax.devices()[0].platform == "tpu" else "interpret"
    grid_k = np.asarray(stkde_tiled(pts, dom, mode=mode))
    err = np.abs(grid - grid_k).max()
    print(f"PB-SYM vs tile-kernel max|diff| = {err:.2e}")
    assert err < 1e-6

    peak = np.unravel_index(grid.argmax(), grid.shape)
    print(f"peak density voxel (x, y, t) = {peak}, "
          f"value = {grid.max():.3e}")
    print(f"total mass = {grid.sum() * dom.sres**2 * dom.tres:.4f} "
          f"(~2/3 per kernel normalization)")

    # what would the planner run on a 256-chip pod?
    tile = (dom.Gx // 16 + 1, dom.Gy // 16 + 1, dom.Gt)
    loads = bucketing.bucket_points_home(pts, dom, tile).counts
    pick, table = choose(dom, len(pts), (16, 16), loads.reshape(-1))
    print(f"\nplanner on a 16x16 pod picks: {pick!r}")
    for name, row in sorted(table.items(), key=lambda kv: kv[1]["total_s"]):
        print(f"  {name:8s} total={row['total_s']*1e3:8.3f}ms "
              f"(init={row['init_s']*1e3:.3f} compute={row['compute_s']*1e3:.3f} "
              f"comm={row['comm_s']*1e3:.3f}) "
              f"{'OK' if row['feasible'] else 'infeasible'}")


if __name__ == "__main__":
    main()

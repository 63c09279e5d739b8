"""Bandwidth exploration (paper Fig. 1): the near-real-time use case.

    PYTHONPATH=src python examples/stkde_interactive.py

The paper's motivation is interactive visual analytics: an analyst sweeps
spatial/temporal bandwidths and the density volume must recompute in
near-real-time. This example sweeps (hs, ht) over a Dengue-like dataset,
prints per-recompute latency, and renders a coarse ASCII heatmap of one
time slice so the smoothing effect is visible.
"""
import time

import numpy as np
import jax

from repro.compile_cache import enable_compile_cache
from repro.core import Domain, pb, clustered_events


ASCII = " .:-=+*#%@"


def ascii_map(slice2d, width=48, height=20):
    h, w = slice2d.shape
    ys = np.linspace(0, h - 1, height).astype(int)
    xs = np.linspace(0, w - 1, width).astype(int)
    sub = slice2d[np.ix_(ys, xs)]
    hi = sub.max() or 1.0
    return "\n".join(
        "".join(ASCII[min(int(v / hi * (len(ASCII) - 1)), len(ASCII) - 1)]
                for v in row)
        for row in sub
    )


def main():
    enable_compile_cache()
    dom0 = Domain(gx=148, gy=194, gt=112, sres=1, tres=1, hs=3, ht=1)
    pts = clustered_events(11_056, dom0, seed=1)   # Dengue-sized
    print(f"events: {len(pts)}, domain {dom0.describe()}\n")

    for hs, ht in [(3, 1), (10, 3), (25, 7)]:
        dom = dom0.with_bandwidth(float(hs), float(ht))
        grid = pb(pts, dom)                       # compile on first call
        jax.block_until_ready(grid)
        t0 = time.perf_counter()
        grid = pb(pts, dom)
        jax.block_until_ready(grid)
        dt = time.perf_counter() - t0
        g = np.asarray(grid)
        t_peak = int(g.sum(axis=(0, 1)).argmax())
        print(f"hs={hs:3d} ht={ht}  recompute {dt * 1e3:7.1f} ms   "
              f"(peak activity at t={t_peak})")
        print(ascii_map(g[:, :, t_peak].T))
        print()


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Smoke run of the STKDE main path on the TPU, at published Table 2 sizes.

    python chip_smoke.py             # one chip
    python chip_smoke.py --instances Dengue_Lr-Hb Flu_Mr-Lb
    python chip_smoke.py --chips 4   # four chips, the mesh strategies only

One chip: PollenUS_Hr-Lb (588,189 points, 651x301x84 grid) and Flu_Mr-Hb
(31,478 points, 233x615x1985 grid), or the Table 2 instances named, through
``stkde()`` three ways: the scatter PB-SYM and the Pallas tile kernel in
compiled mode, each forced, and the default, whose row names the path the
planner chose. Every grid is checked against a float64 NumPy evaluation of
the paper's Algorithm 1 (VB) at a few hundred voxels, half of them around
the densest voxel.

Four chips: Flu_Mr-Hb through every mesh strategy — dr, dd, pd, pd_xt and
dd_lpt on a 2x2 (data, model) mesh, hybrid and pd_xyt on a 2x1x2
(pod, data, model) mesh — each compared with a one-chip ``stkde()`` grid
computed in the same run.

Points come from the seeded generators in ``repro.core.datasets``. Each call
prints one JSON line: first-call seconds (bucketing, compile and run), the
second call's seconds (ended by ``block_until_ready``), the error relative
to the grid maximum, and the ``resilience.fallbacks`` counter. The last line
is ``{"ok": true, "device": {...}}``. The script exits non-zero, without
that line, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
TOL = 1e-4          # max error, relative to the grid maximum
N_VOXELS = 300      # VB reference voxels per instance, half near the peak


class SmokeError(RuntimeError):
    pass


def emit(**row) -> None:
    print(json.dumps(row, default=float), flush=True)


def vb_reference(points: np.ndarray, dom, voxels: np.ndarray) -> np.ndarray:
    """Density at ``voxels`` (m, 3) by Algorithm 1, in float64: every point
    is tested against every voxel with the product Epanechnikov kernel."""
    p = points.astype(np.float64)
    origin = np.array([dom.ox, dom.oy, dom.ot])
    res = np.array([dom.sres, dom.sres, dom.tres])
    centers = origin + (voxels + 0.5) * res
    out = np.empty(len(voxels))
    for i, (xc, yc, tc) in enumerate(centers):
        u = (xc - p[:, 0]) / dom.hs
        v = (yc - p[:, 1]) / dom.hs
        w = (tc - p[:, 2]) / dom.ht
        r2 = u * u + v * v
        ks = np.where(r2 < 1.0, 2.0 / np.pi * (1.0 - r2) ** 2, 0.0)
        kt = np.where(np.abs(w) < 1.0, 0.75 * (1.0 - w * w), 0.0)
        out[i] = (ks * kt).sum()
    return out / (len(p) * dom.hs * dom.hs * dom.ht)


def sample_voxels(grid, dom, seed: int) -> np.ndarray:
    """Half uniform over the grid, half in the bandwidth box around the
    densest voxel (which is included)."""
    import jax.numpy as jnp

    peak = np.array(np.unravel_index(int(jnp.argmax(grid)), dom.grid_shape))
    rng = np.random.default_rng(seed)
    half = N_VOXELS // 2
    hi = np.array(dom.grid_shape)
    uniform = rng.integers(0, hi, size=(half, 3))
    reach = np.array([dom.Hs, dom.Hs, dom.Ht])
    near = np.clip(peak + rng.integers(-reach, reach + 1, size=(half - 1, 3)),
                   0, hi - 1)
    return np.concatenate([peak[None], near, uniform])


def timed(fn):
    """(result, first-call seconds, second-call seconds)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    del out
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, first, time.perf_counter() - t0


def peak_hbm_gb():
    """Peak bytes in use on the first device so far, in GB."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def fallbacks() -> float:
    from repro import obs

    return obs.counter("resilience.fallbacks").value


def check_row(row: dict) -> None:
    emit(**row)
    if not row["max_err_rel"] <= TOL:
        raise SmokeError(f"{row['instance']} {row['path']}: error "
                         f"{row['max_err_rel']:.3e} > {TOL:g} of the max")
    if row["fallbacks"] != 0:
        raise SmokeError(f"{row['instance']} {row['path']}: "
                         f"{row['fallbacks']} fallbacks")


def kernel_is_compiled(pts, dom) -> bool:
    """True when the compiled tile-kernel program holds a Mosaic kernel."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import tiled_inputs
    from repro.kernels.stkde_tile import stkde_tiles_pallas

    lanes, tile_of, tile = tiled_inputs(pts, dom)
    specs = (jax.ShapeDtypeStruct(lanes.shape, jnp.float32),
             jax.ShapeDtypeStruct(tile_of.shape, jnp.int32))
    text = stkde_tiles_pallas.lower(*specs, dom, tile,
                                    len(pts)).compile().as_text()
    return "tpu_custom_call" in text


def chosen_path() -> str:
    """The path of the last ``stkde()`` build, from its root span."""
    from repro.obs import trace

    return trace.get_tracer().spans("stkde")[-1].attrs["path"]


def one_chip(names) -> None:
    import jax.numpy as jnp
    from repro.core import get_instance
    from repro.core.api import stkde

    for name in names:
        inst = get_instance(name)
        dom, pts = inst.domain(), inst.points()
        base = {"instance": name, "n": len(pts),
                "grid": list(dom.grid_shape), "Hs": dom.Hs, "Ht": dom.Ht}
        pb_grid, first, run = timed(
            lambda: stkde(pts, dom, use_tiled_kernel=False, fallback=False))
        voxels = sample_voxels(pb_grid, dom, inst.seed)
        want = vb_reference(pts, dom, voxels)
        scale = float(jnp.max(pb_grid))
        idx = tuple(jnp.asarray(voxels.T))

        def err(grid):
            got = np.asarray(grid[idx], dtype=np.float64)
            return float(np.abs(got - want).max()) / scale

        check_row({**base, "path": "scatter", "first_call_s": first,
                   "run_s": run, "max_err_rel": err(pb_grid),
                   "peak_hbm_gb": peak_hbm_gb(), "fallbacks": fallbacks()})
        tk_grid, first, run = timed(
            lambda: stkde(pts, dom, use_tiled_kernel=True, fallback=False))
        if not kernel_is_compiled(pts, dom):
            raise SmokeError(f"{name}: no tpu_custom_call in the kernel path")
        diff = float(jnp.max(jnp.abs(tk_grid - pb_grid))) / scale
        check_row({**base, "path": "tile_kernel", "first_call_s": first,
                   "run_s": run, "max_err_rel": err(tk_grid),
                   "vs_scatter_rel": diff, "tpu_custom_call": True,
                   "peak_hbm_gb": peak_hbm_gb(), "fallbacks": fallbacks()})
        del tk_grid
        grid, first, run = timed(lambda: stkde(pts, dom, fallback=False))
        check_row({**base, "path": "default", "chose": chosen_path(),
                   "first_call_s": first, "run_s": run,
                   "max_err_rel": err(grid), "fallbacks": fallbacks()})
        del pb_grid, grid


def four_chips(name: str = "Flu_Mr-Hb") -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import get_instance
    from repro.core.api import stkde

    if len(jax.devices()) < 4:
        raise SmokeError(f"--chips 4 needs 4 devices, JAX has "
                         f"{len(jax.devices())}")
    inst = get_instance(name)
    dom, pts = inst.domain(), inst.points()
    base = {"instance": name, "n": len(pts), "grid": list(dom.grid_shape)}
    ref, first, run = timed(lambda: stkde(pts, dom, fallback=False))
    scale = float(jnp.max(ref))
    emit(**base, path="one_chip", first_call_s=first, run_s=run)
    devs = np.array(jax.devices()[:4])
    mesh2 = Mesh(devs.reshape(2, 2), ("data", "model"))
    mesh3 = Mesh(devs.reshape(2, 1, 2), ("pod", "data", "model"))
    runs = [(s, mesh2) for s in ("dr", "dd", "pd", "pd_xt", "dd_lpt")]
    runs += [(s, mesh3) for s in ("hybrid", "pd_xyt")]
    for strat, mesh in runs:
        grid, first, run = timed(lambda: stkde(
            pts, dom, mesh=mesh, strategy=strat, rep_axis=(
                "pod" if mesh is mesh3 else None), fallback=False))
        if len(grid.sharding.device_set) != 4:
            raise SmokeError(f"{strat}: output on "
                             f"{len(grid.sharding.device_set)} devices")
        grid = jax.device_put(grid, ref.sharding)
        diff = float(jnp.max(jnp.abs(grid - ref))) / scale
        del grid
        check_row({**base, "path": strat,
                   "mesh": dict(mesh.shape), "first_call_s": first,
                   "run_s": run, "max_err_rel": diff,
                   "fallbacks": fallbacks()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--instances", nargs="+", metavar="NAME",
                    default=["PollenUS_Hr-Lb", "Flu_Mr-Hb"],
                    help="Table 2 instances for one chip "
                         "(repro.core.datasets.INSTANCES)")
    args = ap.parse_args(argv)
    os.environ.pop("REPRO_FAULTS", None)   # no ambient fault injection
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repro package is not beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.core import plan

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    emit(compile_cache=enable_compile_cache(), jax=jax.__version__,
         hw=dataclasses.asdict(plan.default_hw()))
    try:
        four_chips() if args.chips == 4 else one_chip(args.instances)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

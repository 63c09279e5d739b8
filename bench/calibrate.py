#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, in one process.

    python3 bench/calibrate.py --workload pollenus_hr_lb.build \\
        --seeds 101 102 103 --control-seeds 101 102 103

For each seed, one build through the cell's own entry at its own size,
then the number its run compares (``max_err_rel`` at the sampled voxels)
against the float64 reference: the lower reading is the largest over the
seeds. For each control seed, the same number for the control, the
reference summed in bfloat16 in the program's place: the upper reading is
the smallest. Each reading stands beside the cell's limit with its
verdict, as the run's check would give it: the program's has to come out
correct and the control's not. One JSON line per seed. The benchmark's
runs never run this; it needs the chips the cell asks for.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness, reference, traffic

    cell = harness.find_cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", harness.cache_dir(ROOT))
    cfg = cell.cfg
    limit = cell.check["max_err_rel"]["limit"]
    dom = harness.make_domain(cfg)
    mesh = harness.make_mesh(cfg, devices[:cell.chips])
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        t0 = time.perf_counter()
        points = traffic.events(cfg, cell.traffic, seed)
        grid = jax.block_until_ready(
            harness.stkde_build(points, dom, mesh)())
        voxels, got, want = harness.sample_and_reference(cfg, points, grid,
                                                         seed)
        del grid
        row = {"cell": cell.name, "seed": seed, "voxels": len(voxels),
               "limit": limit}
        if seed in args.seeds:
            row["program"] = reference.max_err_rel(got, want)
            row["program_correct"] = row["program"] <= limit
        if seed in args.control_seeds:
            row["control"] = reference.max_err_rel(
                reference.vb_control(points, cfg, voxels), want)
            row["control_correct"] = row["control"] <= limit
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

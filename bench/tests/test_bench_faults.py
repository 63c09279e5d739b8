"""A run with the timed path broken underneath comes out not correct.

These drive the whole of ``harness.run_cell`` except its look for a chip,
on the tiny cells of ``tiny.py``. The faults are the ones a grid build can
have: the grid returned unchanged from its initial zeros; half of the
events left out, with the density normalised over the rest; the exchange
between chips left out; one answer altered where it is produced.
"""
import io
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness, reference
from bench.tests.tiny import TINY, make_tree

REPO = pathlib.Path(__file__).resolve().parents[2]


def run(root, cell, make_build, seed=17):
    return harness.run_cell(root, cell, seed, 0.2, False,
                            time.perf_counter(), require_tpu=False,
                            make_build=make_build, info_out=open(os.devnull,
                                                                 "w"))


def unchanged(points, dom, mesh):
    import jax.numpy as jnp

    return lambda: jnp.zeros(dom.grid_shape, jnp.float32)


def half_the_events(points, dom, mesh):
    return harness.stkde_build(points[::2], dom, mesh)


def one_answer_altered(points, dom, mesh):
    import jax.numpy as jnp

    build = harness.stkde_build(points, dom, mesh)

    def altered():
        g = build()
        return g.at[jnp.unravel_index(jnp.argmax(g), g.shape)].multiply(1.01)
    return altered


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("bench_tree"))


def test_sound_run_is_correct(tree):
    out = run(tree, "tiny.build", harness.stkde_build)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"build_s", "setup_s"}
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("fault", [unchanged, half_the_events,
                                   one_answer_altered])
def test_fault_is_not_correct(tree, fault):
    out = run(tree, "tiny.build", fault)
    assert not out["correct"]
    check = out["check"]["max_err_rel"]
    assert check["value"] > check["limit"]


def test_a_build_that_raises_counts_as_failed(tree):
    def raises(points, dom, mesh):
        good = harness.stkde_build(points, dom, mesh)
        calls = []

        def build():
            calls.append(1)
            if len(calls) > 1:
                raise RuntimeError("device lost")
            return good()
        return build

    out = run(tree, "tiny.build", raises)
    assert out["failed"] == out["attempted"] >= 1
    # no window build returned a grid, so none is compared: not the warm
    # build's, which was sound; the run is not correct
    assert out["check"]["max_err_rel"]["value"] == np.inf
    assert out["check"]["failed_builds"]["value"] == out["failed"]
    assert not out["correct"]


def test_a_build_starts_only_once_the_last_grid_is_dropped(tmp_path):
    import gc
    import weakref

    held, returned = [], []

    def one_grid(points, dom, mesh):
        good = harness.stkde_build(points, dom, mesh)

        def build():
            gc.collect()    # what stays now is referenced, not garbage
            if returned and returned[-1]() is not None:
                held.append(len(returned))
            grid = good()
            returned.append(weakref.ref(grid))
            return grid
        return build

    out = run(make_tree(tmp_path, min_builds=3), "tiny.build", one_grid)
    assert out["correct"] and out["attempted"] >= 3
    assert len(returned) == out["attempted"] + 1   # the warm build's too
    assert held == []


def test_the_dropped_grid_is_freed_inside_each_window_build(tmp_path,
                                                           monkeypatch):
    order = []
    harness.collect_dropped()          # JAX's own collection runs here
    monkeypatch.setattr(harness, "collect_dropped",
                        lambda: order.append("free"))

    def counted(points, dom, mesh):
        good = harness.stkde_build(points, dom, mesh)

        def build():
            order.append("build")
            return good()
        return build

    out = run(make_tree(tmp_path, min_builds=3), "tiny.build", counted)
    assert out["correct"] and out["attempted"] >= 3
    # the warm build, then before each window build the last grid's release
    assert order == ["build"] + ["free", "build"] * out["attempted"]


def test_the_window_runs_at_least_the_cells_min_builds(tmp_path):
    root = make_tree(tmp_path, min_builds=4)
    assert harness.find_cell(root, "tiny.build").min_builds == 4
    from repro import obs
    before = {p: obs.counter(f"stkde.path.{p}").value
              for p in ("pb", "tiled")}
    info = io.StringIO()
    out = harness.run_cell(root, "tiny.build", 17, 0.0, False,
                           time.perf_counter(), require_tpu=False,
                           info_out=info)
    assert out["correct"] and out["attempted"] == 4
    run_info = json.loads(info.getvalue())["run_info"]
    seconds = run_info["build_seconds"]
    assert len(seconds) == 4 and all(s > 0 for s in seconds)
    # off the TPU every one-device build, the warm one too, takes the
    # scatter; the counters live as long as the process
    took = {p: run_info["path_counters"][p] - before[p] for p in before}
    assert took == {"pb": 5, "tiled": 0}
    assert harness.find_cell(REPO, "flu_mr_hb_x4.build").min_builds == 1


def cell_limit(cell):
    return json.loads((REPO / "bench" / "workloads" / f"{cell}.json")
                      .read_text())["check"]["max_err_rel"]["limit"]


def bfloat16_control(points, dom, mesh):
    """The reference summed in bfloat16, in the program's place: every
    voxel of the grid."""
    import jax.numpy as jnp

    every = np.stack(np.meshgrid(*map(np.arange, TINY["grid"]),
                                 indexing="ij"), -1).reshape(-1, 3)
    grid = jnp.asarray(reference.vb_control(points, TINY, every)
                       .reshape(TINY["grid"]), jnp.float32)
    return lambda: grid


@pytest.mark.parametrize("cell", ["pollenus_hr_lb.build",
                                  "flu_mr_hb_x4.build"])
def test_control_through_the_harness_is_not_correct(tmp_path, cell):
    # the tiny tree holds the real cell's limit
    root = make_tree(tmp_path, limit=cell_limit(cell))
    sound = run(root, "tiny.build", harness.stkde_build)
    assert sound["correct"]
    out = run(root, "tiny.build", bfloat16_control)
    assert out["failed"] == 0
    assert not out["correct"]
    check = out["check"]["max_err_rel"]
    assert check["limit"] == cell_limit(cell) < check["value"] < np.inf


MESH_SCRIPT = """
import json, os, pathlib, sys, time
sys.path[:0] = [{repo!r}, {src!r}]
import numpy as np
from bench import harness
root = pathlib.Path({root!r})

def no_exchange(points, dom, mesh):
    # PD with its halo exchange left out: each device keeps the part of
    # its cylinders that falls outside its block
    from repro.distributed import stkde_dist as sd
    A, B = 2, 2
    gx, gy = sd._device_grid_dims(dom, A, B)
    def build():
        bp, bv = sd.prepare_pd(points, dom, mesh, ("data", "model"))
        out = sd.build_pd(dom, mesh, ("data", "model"), len(points),
                          collectives=False)(bp, bv)
        out = out.reshape(A, B, gx, gy, dom.Gt).transpose(0, 2, 1, 3, 4)
        return out.reshape(A * gx, B * gy, dom.Gt)[:dom.Gx, :dom.Gy]
    return build

for name, make in (("sound", harness.stkde_build), ("fault", no_exchange)):
    r = harness.run_cell(root, "tiny_x4.build", 23, 0.2, False,
                         time.perf_counter(), require_tpu=False,
                         make_build=make, info_out=open(os.devnull, "w"))
    print(json.dumps({{"name": name, "correct": r["correct"],
                       "count": r["device"]["count"],
                       "check": r["check"]}}))
"""


def test_mesh_cell_sound_and_without_exchange(tree):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH_SCRIPT.format(repo=str(REPO), src=str(REPO / "src"),
                              root=str(tree))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = {r["name"]: r for r in map(json.loads,
                                       proc.stdout.strip().splitlines())}
    assert rows["sound"]["correct"] and rows["sound"]["count"] == 4
    assert not rows["fault"]["correct"]
    assert np.isfinite(rows["fault"]["check"]["max_err_rel"]["value"])

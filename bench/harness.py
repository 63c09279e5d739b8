"""One run of one cell: set-up, the measured window, the check, the line.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the configuration in ``bench/configs/<config>.json``, the traffic mix in
``bench/traffic/<traffic>.json``, the cell's correctness limits in
``bench/workloads/<cell>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``. Adding one of them is adding a file.

The window is a closed loop with one caller: whole builds through the
user's entry, ``repro.core.api.stkde(points, dom[, mesh=mesh])`` with
every option at its default, each ended by ``block_until_ready``, back to
back until ``--seconds`` have passed and the cell's ``min_builds`` (in its
workloads file, 1 where it gives none) have run; the build in flight at
the deadline finishes and counts. ``build_s`` is the window's elapsed time
over the builds it completed.

The window holds one grid at a time, as a user holds the one on screen:
each build drops the grid the last one returned, and has JAX free it,
before it starts, inside the timed ``bench.build`` annotation, so freeing
it stays in the window and out of the program's spans. Two grids held at
once would cap a cell at half a chip's memory. The check reads the grid of
the window's last build, and only that one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import re
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import reference, traffic, work

WINDOW_SPAN = "bench.build"
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    # includes the persistent cache's retrieval, which JAX also reports
    # as /jax/compilation_cache/cache_retrieval_time_sec
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = COMPILE_EVENTS[2]
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- discovery
def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict          # the workloads entry of BENCHMARK.json
    cfg: dict
    traffic: dict
    check: dict          # {number: {"limit": .., ...}}
    min_builds: int      # the window runs at least this many builds
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def find_cell(root: pathlib.Path, name: str) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[name]
    d = root / "bench"
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    workload = load_json(d / "workloads" / f"{name}.json")
    return Cell(
        name=name, entry=entry,
        cfg=load_json(d / "configs" / f"{entry['config']}.json"),
        traffic=load_json(d / "traffic" / f"{entry['traffic']}.json"),
        check=workload["check"],
        min_builds=int(workload.get("min_builds", 1)),
        end_to_end=e2e, per_layer=per_layer)


def load_metric(root: pathlib.Path, name: str) -> Callable:
    """The ``read(record)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------- record
@dataclasses.dataclass
class ProgramSpan:
    name: str
    start_s: float
    duration_s: float
    span_id: int
    parent_id: Optional[int]
    attrs: dict


@dataclasses.dataclass
class Record:
    """What a per-layer metric's reader sees of one traced window."""

    cfg: dict
    chips: int
    builds: int
    trace: Any                       # trace_reduce.Trace
    spans: List[ProgramSpan]         # the program's spans in the window
    jax_events: List[tuple]          # (event, seconds) from jax.monitoring
    peaks: Optional[work.Peaks]
    flops: float
    bytes: float


# ------------------------------------------------------------------- run
def cache_dir(root: pathlib.Path) -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")


def make_domain(cfg: dict):
    from repro.core.geometry import Domain

    gx, gy, gt = cfg["grid"]
    return Domain(gx=gx * cfg["sres"], gy=gy * cfg["sres"],
                  gt=gt * cfg["tres"], sres=cfg["sres"], tres=cfg["tres"],
                  hs=cfg["Hs"] * cfg["sres"], ht=cfg["Ht"] * cfg["tres"])


def make_mesh(cfg: dict, devices):
    mesh = cfg.get("mesh")
    if not mesh:
        return None
    from jax.sharding import Mesh

    k = int(np.prod(mesh["shape"]))
    return Mesh(np.array(devices[:k]).reshape(mesh["shape"]),
                tuple(mesh["axes"]))


def stkde_build(points, dom, mesh) -> Callable:
    """The user's call: the path is the program's choice."""
    from repro.core.api import stkde

    if mesh is None:
        return lambda: stkde(points, dom)
    return lambda: stkde(points, dom, mesh=mesh)


def grid_peak(grid):
    import jax.numpy as jnp

    return np.unravel_index(int(jnp.argmax(grid)), grid.shape)


def sample_and_reference(cfg: dict, points, grid, seed: int):
    """(voxels, the grid's values there, the reference's values there)."""
    import jax.numpy as jnp

    voxels = reference.sample_voxels(cfg, points, grid_peak(grid), seed)
    got = np.asarray(grid[tuple(jnp.asarray(voxels.T))], np.float64)
    return voxels, got, reference.vb_reference(points, cfg, voxels)


def collect_dropped() -> None:
    """Run now the release that JAX defers for arrays let go of.

    A grid's host copy, cached on it by the output check, and its device
    buffers go at JAX's next call after the last reference is dropped, and
    that call is the next build's first program span (the points' transfer
    in ``stkde.<s>.bucket``). Collecting here keeps the cost in the
    window but out of the program's spans."""
    from jaxlib import _jax

    _jax.collect_garbage()


def program_path(spans: List[ProgramSpan], mesh) -> str:
    """The strategy the program ran, by its ``stkde.<strategy>`` span."""
    names = sorted({s.name.split(".")[1] for s in spans
                    if re.fullmatch(r"stkde\.[a-z_]+", s.name)})
    if names:
        return ",".join(names)
    return "single device" if mesh is None else "mesh, no strategy span"


def run_cell(root: pathlib.Path, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, require_tpu: bool = True,
             make_build: Callable = stkde_build,
             info_out=sys.stdout) -> dict:
    """Run one cell and return its result object (the last line)."""
    cell = find_cell(root, cell_name)
    cfg = cell.cfg
    import jax
    import jax.monitoring

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform "
                     f"{devices[0].platform!r})")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips, JAX has "
                     f"{len(devices)}")
    used = devices[:cell.chips]
    peaks = work.peaks_for(devices[0].device_kind) if require_tpu else None
    jax.config.update("jax_compilation_cache_dir", cache_dir(root))

    from repro import obs
    from repro.obs import trace as program_trace

    points = traffic.events(cfg, cell.traffic, seed)
    dom = make_domain(cfg)
    mesh = make_mesh(cfg, used)
    build = make_build(points, dom, mesh)
    grid = jax.block_until_ready(build())
    setup_s = time.perf_counter() - t_start

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        program_trace.set_mirror_jax(True)
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    fallbacks = obs.counter("resilience.fallbacks")
    program_trace.reset()
    attempted = failed = 0
    errors: Dict[str, int] = {}
    events: List[tuple] = []

    def on_duration(event, secs, **_):
        events.append((event, secs))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    build_seconds: List[float] = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        before = fallbacks.value
        t_build = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            grid = None       # the last grid goes before the next is built
            collect_dropped()
            try:
                grid = jax.block_until_ready(build())
            except Exception as e:  # a failed build is counted, not fatal
                failed += 1
                errors[type(e).__name__] = errors.get(type(e).__name__,
                                                      0) + 1
            else:
                if fallbacks.value != before:
                    failed += 1
                    grid = None   # not the timed path's grid
        attempted += 1
        now = time.perf_counter()
        build_seconds.append(now - t_build)
        if now >= deadline and attempted >= cell.min_builds:
            break
    window_s = time.perf_counter() - t0
    jax.monitoring.unregister_event_duration_listener(on_duration)
    spans = [ProgramSpan(s.name, s.start_ns / 1e9, s.duration_s, s.span_id,
                         s.parent_id, dict(s.attrs))
             for s in program_trace.get_tracer().spans()]
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        program_trace.set_mirror_jax(False)
        from bench import trace_reduce

        try:
            reduced = trace_reduce.load(log_dir, WINDOW_SPAN,
                                        [d.id for d in used])
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)

    stats = [d.memory_stats() or {} for d in used]
    peak_bytes = [int(s.get("peak_bytes_in_use", 0)) for s in stats]

    # the check, on the grid the window's last build returned; a build
    # that raised or fell back returned no grid, so any such build makes
    # the run not correct, and where the last one did, no grid is compared
    if grid is None:
        err, n_voxels = math.inf, 0
    else:
        voxels, got, want = sample_and_reference(cfg, points, grid, seed)
        del grid
        err, n_voxels = reference.max_err_rel(got, want), len(voxels)
    checks = {"max_err_rel": {"value": err,
                              "limit": cell.check["max_err_rel"]["limit"]},
              "failed_builds": {"value": failed, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    compiles = sum(1 for e, _ in events if e == BACKEND_COMPILE)
    info = {
        "cell": cell.name, "seed": seed, "strategy": program_path(spans, mesh),
        "program_spans": sorted({s.name for s in spans}),
        "builds": attempted, "failed": failed, "errors": errors,
        "fallbacks": fallbacks.value,
        "nonfinite": obs.counter("resilience.nonfinite").value,
        # one-device builds by the path the program took, the warm one too
        "path_counters": {p: obs.counter(f"stkde.path.{p}").value
                          for p in ("pb", "tiled")},
        "compiles_in_window": compiles,
        "cache_retrievals_in_window": sum(
            1 for e, _ in events if e == CACHE_RETRIEVAL),
        "peak_hbm_bytes_per_device": peak_bytes,
        "voxels_compared": n_voxels,
        "build_seconds": build_seconds,
    }

    flops = work.build_flops(len(points), cfg["Hs"], cfg["Ht"])
    nbytes = work.build_bytes(len(points), cfg["grid"])
    if peaks is not None:
        t_min, bound = work.least_time(flops, nbytes, peaks, cell.chips)
        info.update(least_time_s=t_min, roofline_bound=bound)
    print(json.dumps({"run_info": info}), file=info_out, flush=True)

    if trace:
        rec = Record(cfg=cfg, chips=cell.chips, builds=attempted,
                     trace=reduced, spans=spans,
                     jax_events=events, peaks=peaks, flops=flops,
                     bytes=nbytes)
        metrics = {}
        for m in cell.per_layer:
            value = load_metric(root, m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s, "build_s": window_s / attempted}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(peak_bytes)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=reduced.busy_s(), window_s=reduced.window_s)
        result["breakdown"] = {"device_ops": reduced.device_ops(),
                               "idle_gaps": reduced.idle_gaps()}
    result["check"] = checks
    return result


def check_lines(result: dict) -> List[str]:
    return [f"check {k} {v['value']!r} limit {v['limit']!r}"
            for k, v in result["check"].items()]

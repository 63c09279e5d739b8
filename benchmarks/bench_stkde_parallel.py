"""Parallel strategy comparison (paper Figures 8-15 analogues).

Two parts:
  (a) multi-device speedups of DR / DD / PD / DD-LPT / hybrid on 8 fake host
      devices (subprocess — the main process keeps 1 device), including the
      clustered-load case where LPT placement matters (Fig. 13 story), and
      the DD overhead sweep (Fig. 9 story: decomposition multiplies work).
  (b) the coloring/critical-path study (Fig. 12): naive 8-coloring vs
      load-aware coloring T_inf on real instance point distributions, plus
      list-schedule simulated speedups (Graham bound check).
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
from typing import Dict, List

import numpy as np

from repro.core import bench_suite, bucketing, coloring
from repro.distributed import partition
from repro.obs import metrics as obs_metrics, trace as obs_trace

SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")

# All subprocess timing goes through repro.obs.timeit (one warmup +
# block_until_ready code path); spans and metrics are exported through the
# RESULT json and merged into the parent's tracer/registry. Every row names
# the devices of the process that measured it.
_EMIT = r"""
import json
import jax
from jax.sharding import AxisType


def make_mesh(shape, names):
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(shape))


def emit(rows):
    from repro.obs import metrics, trace

    d = jax.devices()
    rows["device"] = {"platform": d[0].platform, "kind": d[0].device_kind,
                      "count": len(d)}
    rows["_trace_events"] = trace.get_tracer().export_events()
    rows["_metrics"] = metrics.export()
    print("RESULT" + json.dumps(rows))
"""

_SUBPROC = r"""
import json
import numpy as np, jax
from repro.core import pb, bench_suite
from repro.distributed.stkde_dist import STRATEGIES
from repro.obs import metrics, timeit, trace

suite = bench_suite(max_voxels=500_000, max_points=8_000)
inst = suite[{name!r}]
dom = inst.domain()
pts = inst.points()

seq = timeit(lambda: pb(pts, dom), name="parallel.seq_pb_sym",
             instance={name!r}).best
rows = {{"instance": {name!r}, "seq_pb_sym_s": seq}}
mesh = make_mesh((4, 2), ("data", "model"))
want = np.asarray(pb(pts, dom))
for strat in ("dr", "dd", "pd", "dd_lpt"):
    fn = STRATEGIES[strat]
    try:
        t = timeit(lambda: fn(pts, dom, mesh), name="parallel." + strat,
                   instance={name!r}).best
        got = np.asarray(fn(pts, dom, mesh))
        ok = bool(np.abs(got - want).max() < 1e-5)
        rows[strat + "_s"] = t
        rows[strat + "_speedup"] = seq / t
        rows[strat + "_correct"] = ok
    except ValueError as e:
        rows[strat + "_s"] = None
        rows[strat + "_note"] = str(e)[:60]
emit(rows)
"""

_RECONCILE_SUBPROC = r"""
import json
import jax
from repro.core import bench_suite
from repro.obs import metrics, reconcile, trace

suite = bench_suite(max_voxels=500_000, max_points=8_000)
inst = suite[{name!r}]
dom = inst.domain()
pts = inst.points()
# 3-axis mesh: pod serves as hybrid's rep axis / pd_xyt's X cut; the
# worker-2D strategies span (data, model) and leave pod replicated
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
out = reconcile.run(pts, dom, mesh, reps={reps})
out["instance"] = {name!r}
emit(out)
"""

_CHAOS_SUBPROC = r"""
import json
import numpy as np, jax
from repro.core import pb, bench_suite
from repro.core.api import stkde
from repro.resilience import faults
from repro.obs import metrics, timeit, trace

suite = bench_suite(max_voxels=500_000, max_points=8_000)
inst = suite[{name!r}]
dom = inst.domain()
pts = inst.points()
mesh = make_mesh((4, 2), ("data", "model"))
want = np.asarray(pb(pts, dom))
reps = {reps}
clean = timeit(lambda: stkde(pts, dom, mesh=mesh, strategy="pd"),
               reps=reps, name="chaos.clean", instance={name!r}).mean
faults.configure({spec!r}, seed={seed})
chaos = timeit(lambda: stkde(pts, dom, mesh=mesh, strategy="pd"),
               reps=reps, name="chaos.injected", instance={name!r}).mean
got = np.asarray(stkde(pts, dom, mesh=mesh, strategy="pd"))
ok = bool(np.abs(got - want).max() < 1e-5)
c = metrics.export()["counters"]
rows = {{"instance": {name!r}, "bench": "chaos", "spec": {spec!r},
        "clean_s": clean, "chaos_s": chaos,
        "recovery_overhead_pct":
            100.0 * (chaos - clean) / clean if clean else None,
        "correct": ok,
        "injected": c.get("resilience.injected", 0),
        "retries": c.get("resilience.retries", 0),
        "fallbacks": c.get("resilience.fallbacks", 0),
        "gave_up": c.get("resilience.gave_up", 0)}}
emit(rows)
"""

_CHUNKED_SUBPROC = r"""
import json, os, tempfile
import numpy as np, jax
from repro.core import get_instance, pb
from repro.core.api import stkde_chunked
from repro.data.pipeline import stkde_stream
from repro.obs import metrics, timeit, trace

inst = get_instance({name!r}).scaled(max_voxels=300_000, max_points={n})
dom = inst.domain()
chunk = {chunk}
mesh = make_mesh((4, 2), ("data", "model"))

# reference: the same points in one monolithic shot (the path the old
# bench_suite 8k-point cap protected); the stream is deterministic, so a
# second pass re-draws the identical point set
all_pts = np.concatenate([c for c, _ in stkde_stream(inst, chunk=chunk)])
mono = timeit(lambda: pb(all_pts, dom), reps={reps},
              name="chunked.mono", instance=inst.name).mean
want = np.asarray(pb(all_pts, dom))

jdir = tempfile.mkdtemp()
def run_once():
    return stkde_chunked(stkde_stream(inst, chunk=chunk), dom, mesh=mesh,
                         strategy="dr", journal=os.path.join(jdir, "j"))
res = run_once()
chunked = timeit(run_once, reps={reps}, name="chunked.run",
                 instance=inst.name).mean
ok = bool(np.abs(res.grid - want).max() < 1e-5)
rows = {{"instance": inst.name, "bench": "chunked", "n": int(inst.n),
        "chunk_size": chunk, "chunks": res.report["chunks_total"],
        "max_chunk_points": res.report["max_chunk_points"],
        "mono_s": mono, "chunked_s": chunked,
        "chunked_overhead_pct":
            100.0 * (chunked - mono) / mono if mono else None,
        "coverage": res.report["coverage"], "correct": ok}}
emit(rows)
"""

_sub_pid = 0   # synthetic pid per subprocess for the merged Chrome trace


def _run_sub(code: str, n_dev: int = 8) -> dict:
    """Run ``code`` in a child on ``n_dev`` virtual XLA:CPU devices.

    The child never asks for the chip (the parent may hold it), and each
    row it returns carries ``device`` with platform "cpu": these sections
    time XLA:CPU, not the TPU.
    """
    global _sub_pid
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    # benchmarks measure; chaos is opt-in per section (run_chaos passes
    # its spec explicitly), so the ambient injection env must not leak
    # into direct-strategy timing subprocesses
    env.pop("REPRO_FAULTS", None)
    proc = subprocess.run([sys.executable, "-c", _EMIT + code], env=env,
                          capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT"):
            r = json.loads(line[len("RESULT"):])
            _sub_pid += 1
            events = r.pop("_trace_events", None)
            if events:
                obs_trace.get_tracer().ingest(events, pid=_sub_pid)
            exported = r.pop("_metrics", None)
            if exported:
                obs_metrics.get_registry().merge(exported)
            return r
    raise RuntimeError("no RESULT line:\n" + proc.stdout[-2000:])


def run_reconcile(instance="Flu_Mr-Hb", quick=False) -> List[Dict]:
    """Planner predicted-vs-measured phase reconciliation (8-device mesh).

    Probes every strategy in the ``obs.reconcile.PROBED`` registry on a
    2x2x2 pod/data/model mesh in the same 8-fake-device subprocess as
    the speedup benchmarks; needs an instance whose 2x2 worker subdomains
    satisfy every strategy's bandwidth constraint (subdomain >= Hs/Ht).
    """
    r = _run_sub(_RECONCILE_SUBPROC.format(
        name=instance, reps=2 if quick else 3))
    print(r["report"])
    return [r]


DEFAULT_CHAOS_SPEC = ("dist.halo:nan:0.2,ckpt.write:corrupt:0.2,"
                      "data.read:drop:0.1")


def run_chaos(instance="Flu_Mr-Hb", spec=DEFAULT_CHAOS_SPEC, seed=42,
              quick=False) -> List[Dict]:
    """Chaos benchmark: the traced api-level query under fault injection.

    Times the same PD query clean and with ``spec`` injection enabled
    (retry + fallback-to-dr absorb the faults), reporting the recovery
    overhead — the number ``make_report.py`` surfaces as the price of
    resilience.
    """
    r = _run_sub(_CHAOS_SUBPROC.format(
        name=instance, spec=spec, seed=seed, reps=3 if quick else 5))
    print(f"  {instance}: clean={r['clean_s']:.3f}s "
          f"chaos={r['chaos_s']:.3f}s "
          f"(+{r['recovery_overhead_pct']:.1f}% recovery overhead; "
          f"{r['injected']:.0f} injected, {r['fallbacks']:.0f} fallbacks, "
          f"correct={r['correct']})")
    return [r]


def run_chunked(instance="Flu_Mr-Hb", quick=False) -> List[Dict]:
    """Chunked-vs-monolithic benchmark at 32k points (4x the bench_suite
    point cap): bounded-memory streamed ingestion + progress journaling
    on the 8-device mesh, priced against the one-shot baseline.
    """
    n = 16_000 if quick else 32_000
    r = _run_sub(_CHUNKED_SUBPROC.format(
        name=instance, n=n, chunk=4096, reps=1 if quick else 2))
    print(f"  {r['instance']}: n={r['n']} in {r['chunks']} chunks "
          f"(max {r['max_chunk_points']} pts buffered), "
          f"mono={r['mono_s']:.3f}s chunked={r['chunked_s']:.3f}s "
          f"(+{r['chunked_overhead_pct']:.1f}%), correct={r['correct']}")
    return [r]


def run_speedups(instances=("Dengue_Lr-Hb", "PollenUS_Lr-Lb", "Flu_Mr-Hb"),
                 quick=False) -> List[Dict]:
    rows = []
    for name in (instances[:1] if quick else instances):
        r = _run_sub(_SUBPROC.format(name=name))
        rows.append(r)
        msg = ", ".join(
            f"{s}={r.get(s + '_speedup'):.2f}x"
            for s in ("dr", "dd", "pd", "dd_lpt")
            if r.get(s + "_speedup") is not None
        )
        print(f"  {name}: seq={r['seq_pb_sym_s']:.3f}s  {msg}")
    return rows


def run_dd_overhead(name="PollenUS_Hr-Mb", decomps=(1, 2, 4, 8, 16)) -> List[
        Dict]:
    """Fig. 9: replication factor (= work overhead) vs decomposition size."""
    suite = bench_suite(max_voxels=500_000, max_points=8_000)
    inst = suite[name]
    dom = inst.domain()
    pts = inst.points()
    rows = []
    for d in decomps:
        tile = (max(1, -(-dom.Gx // d)), max(1, -(-dom.Gy // d)), dom.Gt)
        b = bucketing.bucket_points_overlap(pts, dom, tile)
        rows.append({
            "instance": name, "decomp": f"{d}x{d}x1",
            "replication_factor": round(b.replication_factor, 3),
        })
        print(f"  {name} {d}x{d}: replication "
              f"{b.replication_factor:.3f}x")
    return rows


def run_coloring_study(instances=("Dengue_Lr-Hb", "PollenUS_Hr-Mb",
                                  "Flu_Mr-Hb"),
                       decomp=(16, 16, 4), P=16) -> List[Dict]:
    """Fig. 12/13: T_inf naive vs load-aware; simulated speedups; LPT."""
    suite = bench_suite(max_voxels=500_000, max_points=8_000)
    rows = []
    for name in instances:
        inst = suite[name]
        dom = inst.domain()
        pts = inst.points()
        tile = (max(1, -(-dom.Gx // decomp[0])),
                max(1, -(-dom.Gy // decomp[1])),
                max(1, -(-dom.Gt // decomp[2])))
        b = bucketing.bucket_points_home(pts, dom, tile)
        loads = b.counts.reshape(-1).astype(float)
        shape = b.ntiles
        T1 = loads.sum()
        naive = coloring.naive_coloring(shape)
        smart = coloring.load_aware_coloring(shape, loads)
        tinf_naive = coloring.critical_path(shape, naive, loads)
        tinf_smart = coloring.critical_path(shape, smart, loads)
        sim_naive = coloring.simulate_schedule(shape, naive, loads, P)
        sim_smart = coloring.simulate_schedule(shape, smart, loads, P)
        eff, rep = coloring.replicate_critical(shape, smart, loads, P)
        tinf_rep = coloring.critical_path(shape, smart, eff)
        lpt = partition.imbalance_stats(loads, P)
        rows.append({
            "instance": name,
            "tinf_naive_pct": round(100 * tinf_naive / T1, 2),
            "tinf_sched_pct": round(100 * tinf_smart / T1, 2),
            "tinf_rep_pct": round(100 * tinf_rep / T1, 2),
            "sim_speedup_naive": round(T1 / sim_naive, 2),
            "sim_speedup_sched": round(T1 / sim_smart, 2),
            "graham_bound_sched": round(
                T1 / coloring.graham_bound(T1, tinf_smart, P), 2),
            "lpt_imbalance": round(lpt["lpt_imbalance"], 3),
            "block_imbalance": round(lpt["block_imbalance"], 3),
            "replicated_tasks": int((rep > 1).sum()),
        })
        print(f"  {name}: T_inf {rows[-1]['tinf_naive_pct']}% -> "
              f"{rows[-1]['tinf_sched_pct']}% (sched) -> "
              f"{rows[-1]['tinf_rep_pct']}% (rep); sim speedup "
              f"{rows[-1]['sim_speedup_naive']} -> "
              f"{rows[-1]['sim_speedup_sched']}")
    return rows

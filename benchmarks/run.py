"""Benchmark harness entry point — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only SECTION]

Sections:
  table3     sequential algorithms (paper Table 3)
  parallel   multi-device strategy speedups (Figs. 8/10/11/13/15), on
             8 virtual XLA:CPU devices in a child process
  ddover     DD decomposition overhead (Fig. 9)
  coloring   critical path / scheduling study (Fig. 12)
  kernel     Pallas tile-kernel structural benchmark
  roofline   roofline table from dry-run artifacts (§Roofline)
  serve      continuous-batching vs bucketed serving engine
  chunked    crash-safe chunked execution at 32k points (journal overhead)

Output: ``name,us_per_call,derived`` CSV lines to stdout + JSON to
results/bench/. Rows measured in a child process carry ``device`` (platform,
kind, count): the parallel, chaos and chunked sections time XLA:CPU.
``chip_smoke.py`` at the repository root is the run on the TPU.

With ``--trace``, also writes results/bench/trace.json (Chrome trace —
load in chrome://tracing or Perfetto) and metrics.json, and the parallel
section additionally runs the planner predicted-vs-measured phase
reconciliation (-> reconcile.json + a printed report).

With ``--chaos``, additionally runs the fault-injection benchmark
(``REPRO_FAULTS`` spec override honored): the traced api-level STKDE
query timed clean vs under injection, reporting recovery overhead
(retry/backoff + fallback-to-dr); ``make_report.py`` renders the
resilience section from these rows + metrics.json.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SECTIONS = ("table3", "parallel", "ddover", "coloring", "kernel",
            "roofline", "serve", "chunked")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", nargs="*", default=list(SECTIONS),
                    choices=SECTIONS)
    ap.add_argument("--out", default="results/bench")
    ap.add_argument("--trace", action="store_true",
                    help="export Chrome trace + metrics + reconciliation")
    ap.add_argument("--chaos", action="store_true",
                    help="add the fault-injection benchmark (recovery "
                         "overhead; REPRO_FAULTS overrides the spec)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    all_results = {}

    if "table3" in args.only:
        print("== table3: sequential algorithm comparison ==")
        from benchmarks import bench_stkde_table3
        all_results["table3"] = bench_stkde_table3.run(quick=args.quick)
    if "parallel" in args.only:
        print("== parallel: strategy speedups (8 devices) ==")
        from benchmarks import bench_stkde_parallel
        all_results["parallel"] = bench_stkde_parallel.run_speedups(
            quick=args.quick)
        if args.trace:
            print("== parallel: planner reconciliation (8 devices) ==")
            all_results["reconcile"] = bench_stkde_parallel.run_reconcile(
                quick=args.quick)
            with open(os.path.join(args.out, "reconcile.json"), "w") as f:
                json.dump(all_results["reconcile"], f, indent=1,
                          default=float)
    if "ddover" in args.only:
        print("== ddover: DD replication overhead (Fig 9) ==")
        from benchmarks import bench_stkde_parallel
        all_results["ddover"] = bench_stkde_parallel.run_dd_overhead()
    if "coloring" in args.only:
        print("== coloring: critical path & scheduling (Fig 12) ==")
        from benchmarks import bench_stkde_parallel
        all_results["coloring"] = bench_stkde_parallel.run_coloring_study()
    if "kernel" in args.only:
        print("== kernel: Pallas tile structure ==")
        from benchmarks import bench_kernel
        all_results["kernel"] = bench_kernel.run(quick=args.quick)
    if "roofline" in args.only:
        print("== roofline: dry-run derived table ==")
        from benchmarks import bench_roofline
        if os.path.isdir("results/dryrun/single"):
            all_results["roofline"] = bench_roofline.run()
        else:
            print("  (no dry-run artifacts; run repro.launch.dryrun first)")
    if "serve" in args.only:
        print("== serve: continuous vs bucketed engine ==")
        from benchmarks import bench_serve
        all_results["serve"] = bench_serve.run(quick=args.quick)
    if "chunked" in args.only:
        print("== chunked: crash-safe chunked STKDE at 32k points ==")
        from benchmarks import bench_stkde_parallel
        all_results["chunked"] = bench_stkde_parallel.run_chunked(
            quick=args.quick)

    if args.chaos:
        print("== chaos: fault-injection recovery overhead (8 devices) ==")
        from benchmarks import bench_stkde_parallel
        spec = os.environ.get(
            "REPRO_FAULTS", bench_stkde_parallel.DEFAULT_CHAOS_SPEC)
        seed = int(os.environ.get("REPRO_FAULTS_SEED", "42"))
        all_results["chaos"] = bench_stkde_parallel.run_chaos(
            spec=spec, seed=seed, quick=args.quick)

    with open(os.path.join(args.out, "results.json"), "w") as f:
        json.dump(all_results, f, indent=1, default=float)

    if args.trace:
        from repro.obs import metrics as obs_metrics, trace as obs_trace
        tpath = os.path.join(args.out, "trace.json")
        obs_trace.save_chrome_trace(tpath)
        obs_metrics.save_json(os.path.join(args.out, "metrics.json"))
        n_ev = len(obs_trace.get_tracer().to_chrome_trace()["traceEvents"])
        print(f"\n[obs] {n_ev} events -> {tpath} (chrome://tracing), "
              f"metrics -> {args.out}/metrics.json")

    # required CSV summary: name,us_per_call,derived
    print("\nname,us_per_call,derived")
    for section, rows in all_results.items():
        for r in rows:
            name = r.get("instance") or r.get("cell") or r.get("bench") or \
                r.get("decomp", "?")
            t = None
            for k in ("pb_sym_s", "seq_pb_sym_s", "scatter_pb_s"):
                if r.get(k) is not None:
                    t = r[k] * 1e6
                    break
            derived = (r.get("sym_speedup") or r.get("dr_speedup")
                       or r.get("bottleneck") or r.get("mxu_fill")
                       or r.get("replication_factor")
                       or r.get("tinf_sched_pct")
                       or r.get("recovery_overhead_pct")
                       or r.get("chunked_overhead_pct")
                       or r.get("tokens_per_s") or "")
            print(f"{section}:{name},{'' if t is None else round(t, 1)},"
                  f"{derived}")


if __name__ == "__main__":
    main()

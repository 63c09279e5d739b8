#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload pollenus_hr_lb.build --seed 7 \\
        --seconds 10 --trace 0

Prints one JSON line of run details, then, as its last line, the result:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, traced, ``breakdown``; ``check`` comes last, each number
compared beside its limit, and the same comparison ends standard error.
Exits non-zero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for, or when the program under test is not in the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.pop("REPRO_FAULTS", None)   # no injected faults in a run
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in harness.check_lines(result):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A tree of benchmark files at a size a CPU test can run: the real
traffic mixes and metric readers, with tiny configurations beside them."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

TINY = {
    "name": "tiny", "source": "test", "n": 3000, "grid": [40, 36, 24],
    "sres": 1.0, "tres": 1.0, "Hs": 3, "Ht": 2, "precision": "float32",
    "chips": 1, "mesh": None,
    "geography": {"seed": 2, "clusters": 6, "cluster_frac": 0.8},
    "assumed": {}, "reduced": [],
}
TINY_X4 = dict(TINY, name="tiny_x4", chips=4,
               mesh={"shape": [2, 2], "axes": ["data", "model"]})


def make_tree(root: pathlib.Path, limit: float = 1e-4,
              min_builds: int = 1) -> pathlib.Path:
    """Write BENCHMARK.json and bench/ under ``root`` for the two tiny
    cells ``tiny.build`` (one device) and ``tiny_x4.build`` (2x2 mesh),
    each held to ``limit`` and running at least ``min_builds`` builds."""
    d = root / "bench"
    for sub in ("configs", "traffic", "workloads", "metrics"):
        (d / sub).mkdir(parents=True, exist_ok=True)
    shutil.copy(BENCH / "traffic" / "build.json", d / "traffic")
    for f in (BENCH / "metrics").glob("*.py"):
        shutil.copy(f, d / "metrics")
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = []
    for cfg in (TINY, TINY_X4):
        (d / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
        cell = f"{cfg['name']}.build"
        (d / "workloads" / f"{cell}.json").write_text(json.dumps(
            {"check": {"max_err_rel": {"limit": limit}},
             "min_builds": min_builds}))
        cells.append({"name": cell, "config": cfg["name"],
                      "traffic": "build", "chips": cfg["chips"],
                      "why": "test"})
    per_layer = [dict(m, workloads=[c["name"] for c in cells])
                 for m in real["per_layer"]]
    (root / "BENCHMARK.json").write_text(json.dumps(dict(
        real, workloads=cells, per_layer=per_layer,
        configs=[{"name": c["name"], "source": "test",
                  "file": f"bench/configs/{c['name']}.json", "reduced": [],
                  "why": "test"} for c in (TINY, TINY_X4)])))
    return root

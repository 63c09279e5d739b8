"""Configurations, cells and metrics are found by name: adding one is
adding a file. And the entry refuses to run without a chip."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

from bench import harness
from bench.tests.tiny import make_tree

REPO = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_dropped_in_files_are_found_by_name(tmp_path):
    root = make_tree(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    # a new configuration, cell and per-layer metric, each a file of its
    # own plus its entry in BENCHMARK.json; no existing file under bench/
    # is edited
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    (root / "bench/configs/tiny_b.json").write_text(
        json.dumps(dict(cfg, name="tiny_b", Hs=4)))
    (root / "bench/workloads/tiny_b.build.json").write_text(
        json.dumps({"check": {"max_err_rel": {"limit": 2e-4}}}))
    (root / "bench/metrics/builds_seen.py").write_text(
        "def read(rec):\n    return float(rec.builds)\n")
    bench["configs"].append({"name": "tiny_b", "source": "test",
                             "file": "bench/configs/tiny_b.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_b.build", "config": "tiny_b",
                               "traffic": "build", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "builds_seen", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "build_s",
                               "workloads": ["tiny_b.build"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == b for p, b in before.items())

    cell = harness.find_cell(root, "tiny_b.build")
    assert cell.cfg["Hs"] == 4 and cell.chips == 1
    assert cell.check["max_err_rel"]["limit"] == 2e-4
    assert cell.traffic["seed_draws"] == "events"
    assert [m["name"] for m in cell.per_layer] == ["builds_seen"]
    assert [m["name"] for m in cell.end_to_end] == ["build_s", "setup_s"]
    read = harness.load_metric(root, "builds_seen")
    assert read(harness.Record(cfg=cell.cfg, chips=1, builds=3,
                               trace=None, spans=[], jax_events=[],
                               peaks=None, flops=0, bytes=0)) == 3.0
    assert "builds_seen" not in [
        m["name"] for m in harness.find_cell(root, "tiny.build").per_layer]


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()
        assert json.loads((REPO / c["file"]).read_text())["name"] == c["name"]
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert (REPO / "bench/traffic" / f"{w['traffic']}.json").is_file()
        cell = harness.find_cell(REPO, w["name"])
        assert cell.check["max_err_rel"]["limit"] > 0
        assert {"setup_s", "build_s"} <= {m["name"] for m in cell.end_to_end}
        assert cell.per_layer
    for m in bench["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(harness.load_metric(REPO, m["name"]))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_entry_refuses_without_a_chip_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload",
           "pollenus_hr_lb.build", "--seed", "1", "--seconds", "1",
           "--trace", "0"]
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no TPU" in proc.stderr
    # a checkout holding only BENCHMARK.json and the benchmark's files
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""

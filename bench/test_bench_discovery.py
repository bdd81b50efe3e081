"""A configuration (with its own generator), a traffic mix and a metric
added as new files plus new BENCHMARK.json entries, with no edit to a
file that is there, are found by name and run."""
import json
import os
import shutil

from bench import graphdata, harness
from bench.conftest import run_tiny

GRID = '''
import numpy as np


def edges(cfg, rng):
    side = int(cfg["side"])
    idx = np.arange(side * side).reshape(side, side)
    src = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel()])
    dst = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel()])
    return src, dst, side * side
'''

LANES = '''
def read(run):
    return 8 * len(run.items) / run.window_s
'''


def test_new_files_and_entries_are_found_by_name(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "xla")
    root = str(tmp_path)
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(harness.HERE, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(root, "bench", p), "rb").read()
              for p in os.listdir(os.path.join(root, "bench"))
              if p.endswith(".py")}
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "graphs", "grid.py"), "w") as f:
        f.write(GRID)
    cfg = {"name": "grid-32", "generator": "grid", "side": 32,
           "graph_seed": 0, "weights": None}
    cfg["search_keys"] = graphdata.search_keys(cfg, 16, b).tolist()
    with open(os.path.join(b, "configs", "grid-32.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "reach-batches.json"), "w") as f:
        json.dump({"driver": "batches", "primitive": "reach", "batch": 8,
                   "hops": 2, "check_batches": 4,
                   "limits": {"reach_mismatches": 0}}, f)
    with open(os.path.join(b, "metrics", "lanes_per_s.py"), "w") as f:
        f.write(LANES)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "grid-32", "source": "a 2-D grid",
                          "file": "bench/configs/grid-32.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "grid-reach", "config": "grid-32",
                            "traffic": "reach-batches", "chips": 1,
                            "why": "test"})
    for m in bm["end_to_end"]:
        if m["name"] == "teps":
            m["workloads"].append("grid-reach")
    bm["per_layer"].append({"name": "lanes_per_s", "unit": "lanes/s",
                            "better": "higher", "source": "host_clock",
                            "layer": "enactor", "moves": "teps",
                            "workloads": ["grid-reach"]})
    with open(path, "w") as f:
        json.dump(bm, f)

    spec = harness.load_spec("grid-reach", root=root, bench_dir=b)
    assert spec.config["generator"] == "grid"
    result, run = run_tiny(spec)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s", "teps"}
    assert run.graph.stored_edges == 2 * 2 * 32 * 31
    result, _ = run_tiny(spec, trace=True)
    assert "lanes_per_s" in result["metrics"]
    assert result["checks"]["reach_mismatches"]["value"] == 0
    for p, data in before.items():
        assert open(os.path.join(b, p), "rb").read() == data

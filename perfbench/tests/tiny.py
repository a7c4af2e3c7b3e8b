"""A tiny copy of the benchmark for tests on the CPU: the harness and its
files copied into a temporary root, with a tiny configuration, traffic mixes
and cells added as new files beside the real ones."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)

TINY_CONFIG = {
    "intermediate_size": 256, "hidden_size": 512,
    "initializer_range": 0.02,
    "sparsity": {"density": 0.03125}, "pattern_seed": 0,
    "format": "incrs",
    "stored": {"values": "float32", "indices": "int32"},
    "check": {"max_rel_err": 0.01},
}
TRAFFIC = {
    "tiny-open": {"loop": "open", "rate_per_s": 12,
                  "widths": {"dist": "bounded_pareto", "min": 8, "max": 64,
                             "alpha": 0.5},
                  "max_wave_cols": 128, "variant": "auto", "pool_cols": 256,
                  "compare": 6},
    "tiny-closed": {"loop": "closed", "clients": 2,
                    "widths": {"dist": "list", "values": [64, 192, 128]},
                    "max_wave_cols": 128, "variant": "auto",
                    "pool_cols": 256, "compare": 4},
}


def make(tmp: str) -> str:
    """Copy the benchmark into ``tmp`` and add the tiny cells as new files;
    returns the new root."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(HERE, os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg_dir = os.path.join(root, "perfbench", "configs")
    with open(os.path.join(cfg_dir, "tiny-pruned.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    shutil.copy(os.path.join(cfg_dir, "mixtral-8x7b-w1-d50.py"),
                os.path.join(cfg_dir, "tiny-pruned.py"))
    for name, mix in TRAFFIC.items():
        with open(os.path.join(root, "perfbench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(mix, f)
    bench["configs"].append({
        "name": "tiny-pruned", "source": "test", "reduced": [],
        "file": "perfbench/configs/tiny-pruned.json", "why": "test"})
    for name, mix in (("tiny-open", "tiny-open"),
                      ("tiny-closed", "tiny-closed")):
        bench["workloads"].append({"name": name, "config": "tiny-pruned",
                                   "traffic": mix, "chips": 1,
                                   "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kind = "latency" if m["name"] == "p50_ms" \
                or m["name"].endswith(".latency") else "throughput"
            m["workloads"].append("tiny-open" if kind == "latency"
                                  else "tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root

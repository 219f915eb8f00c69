"""Shared by the benchmark's own tests: where things are, and a temporary
copy of `benchmark/` with the tiny test cells added as files (nothing that
exists is edited)."""
import json
import os
import shutil


REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

TINY_CELLS = [
    {"name": "qwen2-tiny.closed", "config": "qwen2-tiny",
     "traffic": "closed_tiny", "chips": 1, "why": "test"},
    {"name": "opt-tiny.train", "config": "opt-tiny",
     "traffic": "train_tiny", "chips": 1, "why": "test"},
]


def make_root(tmp: str) -> str:
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic"):
        for f in os.listdir(os.path.join(DATA, sub)):
            shutil.copy(os.path.join(DATA, sub, f),
                        os.path.join(tmp, "benchmark", sub, f))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] += TINY_CELLS
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads")
        if cells and any(c.endswith("decode_closed") for c in cells):
            cells.append("qwen2-tiny.closed")
        if cells and any(c.endswith("train_1chip") for c in cells):
            cells.append("opt-tiny.train")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp

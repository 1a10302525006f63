"""A temporary copy of the benchmark with the four-chip cell of
``later/train-internlm2-1.8b-fsdp4.json`` added the way a later PR would add
it: one entry, one limits file, its name on the metrics it joins."""

import json
import os
import shutil

from benchmark.lib import harness

PKG = "pytorch_distributed_training_tutorials_tpu"
LATER = os.path.join(os.path.dirname(__file__), "later",
                     "train-internlm2-1.8b-fsdp4.json")


def copy_of_benchmark(root: str) -> dict:
    """benchmark/ and the program under ``root``; returns BENCHMARK.json."""
    shutil.copytree(harness.BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(harness.ROOT, PKG), os.path.join(root, PKG))
    return harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def with_four_chip_cell(root: str) -> str:
    spec = copy_of_benchmark(root)
    later = harness.read_json(LATER)
    name = later["workload"]["name"]
    spec["workloads"].append(later["workload"])
    spec["per_layer"] += later["per_layer"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in later["joins"]:
            m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    with open(os.path.join(root, "benchmark", "limits", name + ".json"), "w") as f:
        json.dump({"limits": later["limits"]}, f)
    return name

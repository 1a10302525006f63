"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric, or a whole block with a configuration and a cell of it, as files
and one entry each in ``BENCHMARK.json``, and edits no file that exists:
shown on a temporary copy."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import harness
from benchmark.tests import later_cell


def digest(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in base:
            continue
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def rehearse(root, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload",
         cell, "--seed", "4", "--seconds", "1", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=root, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_add_a_cell_by_adding_files(tmp_path):
    root = str(tmp_path)
    spec = later_cell.copy_of_benchmark(root)
    before = digest(root)
    bench = os.path.join(root, "benchmark")

    config = harness.read_json(os.path.join(bench, "configs", "mistral-7b-v0.1.json"))
    for k, v in config.pop("rehearse").items():  # the toy widths, for good
        config[k] = {**config[k], **v} if isinstance(v, dict) else v
    config["name"] = "toy-32to8"
    with open(os.path.join(bench, "configs", "toy-32to8.json"), "w") as f:
        json.dump(config, f)
    mix = {"kind": "closed_clients", "clients": 3, "block": 4,
           "check_requests": 2, "pairing_seed": 1,
           "prompt": {"dist": "uniform", "min": 9, "max": 60},
           "output": {"dist": "uniform", "min": 3, "max": 9}}
    with open(os.path.join(bench, "traffic", "toy_batch.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "limits", "toy.batch.json"), "w") as f:
        json.dump({"limits": {"served_token_gap": 0.5, "tokens_compared_min": 4}}, f)
    with open(os.path.join(bench, "layer_metrics", "steps_per_request.toy.py"), "w") as f:
        f.write("def read(bundle):\n    c = bundle['counters']\n"
                "    return c['steps'] / max(1, c['requests_done'])\n")

    spec["configs"].append({
        "name": "toy-32to8", "source": "https://example.org/toy", "reduced": [],
        "file": "benchmark/configs/toy-32to8.json", "why": "a toy"})
    spec["workloads"].append({
        "name": "toy.batch", "config": "toy-32to8", "traffic": "toy_batch",
        "chips": 1, "why": "a toy"})
    spec["per_layer"].append({
        "name": "steps_per_request.toy", "unit": "steps", "better": "lower",
        "source": "program_counter", "layer": "serving loop",
        "moves": "tpot_mean_ms", "workloads": ["toy.batch"]})
    for m in spec["end_to_end"]:
        if m["name"] == "tpot_mean_ms":
            m["workloads"].append("toy.batch")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    line = rehearse(root, "toy.batch")
    assert line["correct"] is True and line["attempted"] > 0
    assert "steps_per_request.toy" in line["metrics"]
    assert "compile_s" in line["metrics"]  # a metric with no workloads key
    after = digest(root)
    assert {k: after[k] for k in before} == before  # no existing file edited
    assert len(after) == len(before) + 4


SECOND = os.path.join(os.path.dirname(__file__), "second_block")


@pytest.mark.parametrize("fault", [None, "top_k_one_lower"])
def test_add_a_block_by_adding_files(fault, tmp_path):
    """``second_block/`` is what a ``model_config`` PR of another block
    brings: the block's two files, a configuration naming it, a mix, limits
    and the entries. Routed experts through ``TransformerConfig.moe_experts``:
    another parameter tree, other equations, other operation counts."""
    root = str(tmp_path)
    spec = later_cell.copy_of_benchmark(root)
    before = digest(root)
    bench = os.path.join(root, "benchmark")
    entries = harness.read_json(os.path.join(SECOND, "entries.json"))
    for part in ("blocks", "configs", "traffic", "limits"):
        shutil.copytree(os.path.join(SECOND, part), os.path.join(bench, part),
                        dirs_exist_ok=True)
    spec["configs"].append(entries["config"])
    spec["workloads"].append(entries["workload"])
    cell = entries["workload"]["name"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in entries["joins"]:
            m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    if fault:  # in the new block's own program half: one expert a token fewer
        path = os.path.join(bench, "blocks", "routed_swiglu", "program.py")
        with open(path) as f:
            text = f.read()
        assert text.count("moe_top_k=top_k") == 1
        with open(path, "w") as f:
            f.write(text.replace("moe_top_k=top_k", "moe_top_k=top_k - 1"))

    line = rehearse(root, cell)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is (fault is None), line["checks"]
    after = digest(root)
    assert {k: after[k] for k in before} == before  # no existing file edited
    added = sorted(set(after) - set(before))
    assert added == sorted(
        os.path.join("benchmark", p) for p in (
            "blocks/routed_swiglu/reference.py", "blocks/routed_swiglu/program.py",
            "configs/toy-routed.json", "traffic/toy_steps.json",
            "limits/toy.routed.json"))

    # another tree and other counts than the first block's
    first = harness.Block("gqa_swiglu").reference
    second = harness.Block("routed_swiglu", root).reference
    config = harness.read_json(os.path.join(bench, "configs", "toy-routed.json"))
    a, b = first.Shape.from_config(config), second.Shape.from_config(config)
    assert set(second.leaf_shapes(b)["layers"]) != set(first.leaf_shapes(a)["layers"])
    assert second.total_params(b) != first.total_params(a)
    assert second.train_flops_per_token(b, 32) != first.train_flops_per_token(a, 32)

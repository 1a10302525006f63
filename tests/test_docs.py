"""The documents a session reads first describe the tree that is there.

PR 32 found ``README.md`` and ``CLAUDE.md`` sending every new session to
measuring instruments that ``benchmark/`` had replaced, and naming none of
``benchmark/run.py``, ``PERF_LEDGER.jsonl`` or ``PERF.md``. These tests
hold the line: a named file exists, a retired instrument is named nowhere,
both documents say how the repo is measured, and ``PERF.md`` argues every
cell and every end-to-end metric ``BENCHMARK.json`` declares. Plain text
checks: no jax, milliseconds.
"""

import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
PACKAGE = REPO / "pytorch_distributed_training_tutorials_tpu"
BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())

# ``benchmark/README.md`` is on none of the lists: only a PR of kind
# ``benchmark`` may repair it.
DOCS = ["README.md", "CLAUDE.md", ".claude/skills/verify/SKILL.md"]

# What PR 32 deleted: names, then the scripts' and the records' files.
RETIRED = [
    "StepReport", "classify_hlo", "device_op_durations", "lm_headline",
    "bench.regress", "bench/regress", "regress.py", "obs/trace.py",
    "receipt_session", "model_flops_per_token",
    "profile_step.py", "profile_decode.py", "train_llm_mfu.py",
    "int8_decode_sweep.py", "flash_bench.py", "step_time_experiment.py",
    "epoch_gather_experiment.py",
    "TRAIN_LLM_r05.json", "SERVING_r04.json", "SERVING_r04_gqa.json",
    "SERVING_r04_long.json", "SERVING_r05_long_int8.json",
    "SERVING_r05_long_int8_mha.json", "SCALING_cpu.json", "SCALING_r05.json",
]

# A back-ticked path: no blank inside, a known ending, ``:line`` allowed.
_NAMED_FILE = re.compile(
    r"`([^`\s]+\.(?:py|jsonl|json|md|ipynb))(?::[\d,-]+)?`"
)


def _texts(where: str):
    """(path, text) of a file, or of every text file under a directory."""
    root = REPO / where
    paths = [root] if root.is_file() else sorted(root.rglob("*"))
    for path in paths:
        if not path.is_file() or "__pycache__" in path.parts:
            continue
        if path == Path(__file__).resolve():
            continue  # this file holds the list
        try:
            yield path, path.read_text()
        except UnicodeDecodeError:
            continue  # a built extension


@pytest.mark.parametrize("doc", DOCS)
def test_named_files_exist(doc):
    names = set(_NAMED_FILE.findall((REPO / doc).read_text()))
    # a pattern, a placeholder, or a path outside the checkout
    names = {n for n in names if not (set("*<") & set(n) or n.startswith("/"))}
    assert names, f"{doc} names no file: the pattern no longer fits it"
    missing = sorted(
        n for n in names
        if not any(
            (root / n).exists()
            for root in (REPO, PACKAGE, REPO / "benchmark", REPO / "tests")
        )
    )
    assert missing == [], f"{doc} names files that do not exist"


@pytest.mark.parametrize("where", [
    *DOCS, "notebooks/build_notebooks.py",
    "pytorch_distributed_training_tutorials_tpu", "scripts", "examples", "tests",
])
def test_names_no_retired_instrument(where):
    found = sorted(
        f"{path.relative_to(REPO)}: {name}"
        for path, text in _texts(where)
        for name in RETIRED if name in text
    )
    assert found == []


@pytest.mark.parametrize("doc", ["README.md", "CLAUDE.md"])
def test_says_how_the_repo_is_measured(doc):
    text = (REPO / doc).read_text()
    for needed in (" ".join(BENCHMARK["command"]), "PERF_LEDGER.jsonl",
                   "PERF.md"):
        assert needed in text, f"{doc} does not name {needed}"


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_perf_md_has_every_cell(cell):
    assert f"`{cell}`" in (REPO / "PERF.md").read_text()


@pytest.mark.parametrize(
    "metric", [m["name"] for m in BENCHMARK["end_to_end"]]
)
def test_perf_md_has_every_end_to_end_metric(metric):
    assert f"`{metric}`" in (REPO / "PERF.md").read_text()


# The form the driver holds ``BENCHMARK.json`` to before any run (PR 36 was
# refused for a ``why`` of 203 characters).
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
_UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and text.isascii() and text.isprintable()


@pytest.mark.parametrize("entry", [
    pytest.param((group, e), id=f"{group}:{e['name']}")
    for group in _KEYS for e in BENCHMARK[group]
])
def test_benchmark_json_entry_has_the_drivers_form(entry):
    group, e = entry
    assert set(e) - {"workloads"} == _KEYS[group]
    names = [e["name"], *e.get("reduced", []), *e.get("workloads", [])]
    names += [e[k] for k in ("config", "traffic", "moves") if k in e]
    assert [n for n in names if not _NAME.fullmatch(n)] == []
    lines = [e[k] for k in ("why", "source", "layer") if k in e]
    assert [t for t in lines if not _one_line(t)] == []
    if "unit" in e:
        assert _UNIT.fullmatch(e["unit"]) and e["better"] in ("lower", "higher")
    if "file" in e:
        assert e["file"].startswith(tuple(p + "/" for p in BENCHMARK["paths"]))
        assert (REPO / e["file"]).is_file() and len(e["reduced"]) <= 16


def test_benchmark_json_as_a_whole_has_the_drivers_form():
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert all(_one_line(word) for word in BENCHMARK["command"])
    cells = BENCHMARK["workloads"]
    for group in _KEYS:
        names = [e["name"] for e in BENCHMARK[group]]
        assert len(names) == len(set(names)), group
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    configs = {c["name"] for c in BENCHMARK["configs"]}
    assert {w["config"] for w in cells} == configs
    files = [c["file"] for c in BENCHMARK["configs"]]
    assert len(files) == len(set(files))
    assert all(w["chips"] in (1, 4) for w in cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    known = {w["name"] for w in cells}
    ends = {m["name"] for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert set(m.get("workloads", [])) <= known, m["name"]
        assert m.get("moves", m["name"]) in ends, m["name"]
    # 2 + 14 runs a cell of run_seconds + 60, 2 x 90 s a cell, 1200 s spare
    runs = 2 + 14 * len(cells)
    seconds = runs * (BENCHMARK["run_seconds"] + 60) + 180 * len(cells) + 1200
    assert seconds <= 43200

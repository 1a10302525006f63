"""``run.py --rehearse`` of every cell at toy widths on the CPU: a
well-formed last line that names ``cpu`` and carries counts only."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import harness

SPEC = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in SPEC["workloads"]]


def rehearse(cell, trace, seed=2**31 + 7):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_line(cell, trace):
    line, err = rehearse(cell, trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks" and line["checks"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    chips = next(w["chips"] for w in SPEC["workloads"] if w["name"] == cell)
    assert line["device"]["count"] == chips
    # counts only: no CPU number under a device metric's name
    assert all(m["value"] is None for m in line["metrics"].values())
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in SPEC[kind]
             if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) <= names and line["metrics"]
    assert "setup_s" in line["metrics"] or trace
    # each number compared is printed beside its limit as the last lines
    tail = [l for l in err.strip().splitlines() if l.startswith("check ")]
    assert len(tail) == len(line["checks"])


def test_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT, timeout=300,
    )
    assert out.returncode == 3 and out.stdout.strip() == ""
    assert "refusing to measure" in out.stderr

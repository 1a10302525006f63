"""``chip_smoke.py --rehearse``: the chip smoke's control flow on the CPU.

The smoke itself needs a TPU (the driver runs it there after every PR);
this runs the same phases at toy widths in a child process, Pallas in
interpret mode, so a change that breaks the script is seen by tier-1. A
rehearsal is never a chip run: its last line must name the CPU.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, as on one chip
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    return out, lines


def test_rehearsal_runs_every_phase_and_names_the_cpu():
    out, lines = _smoke("--rehearse")
    assert out.returncode == 0, out.stderr[-2000:]
    phases = [l["phase"] for l in lines[:-1]]
    assert phases == [
        "device", "train", "serve", "serve_paged_gather",
        "serve_paged_kernel", "serve_state", "serve_parallel", "total",
    ]
    assert all(l["ok"] is True for l in lines), lines
    assert lines[-1] == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    by = {l["phase"]: l for l in lines[:-1]}
    assert by["device"]["rehearse"] is True
    assert by["train"]["pallas_interpret"] is True
    assert by["train"]["builds_after_first_step"] == 0
    assert by["serve"]["n_prefill_errors"] == 0
    assert by["serve"]["builds_after_warmup"] == 0
    assert by["serve_paged_kernel"]["options"]["paged_kernel"] is True
    state = by["serve_state"]  # a prompt longer than the ring, with state
    assert state["prompt_len"] > state["model"]["ring"]
    assert state["generate_greedy_gap_rel"] <= 0.1
    assert state["slot_state_bytes"] > 0 and state["slot_ring_bytes"] > 0
    both = by["serve_parallel"]  # a state and a KV cache in one layer
    assert both["generate_greedy_gap_rel"] <= 0.1
    assert both["slot_state_bytes"] > 0 and both["slot_kv_bytes"] > 0
    assert len(both["prompt_lens"]) > 2  # more requests than slots: a refill


def test_four_chip_rehearsal_runs_only_the_multichip_phase():
    """``--chips 4 --rehearse``: four virtual CPU devices, device 0 alone
    against DataParallel and FSDP, and no one-chip phase."""
    out, lines = _smoke("--rehearse", "--chips", "4")
    assert out.returncode == 0, out.stderr[-2000:]
    assert [l["phase"] for l in lines[:-1]] == ["device", "multichip", "total"]
    assert lines[-1]["device"] == {
        "platform": "cpu", "kind": "cpu", "count": 4,
    }
    multi = lines[1]
    assert multi["ok"] is True and multi["failed"] == []
    assert multi["data_parallel"]["all_reduce_in_step"] is True
    assert multi["fsdp"]["batch_shard_shapes"] == [[1, 64]] * 4
    assert max(multi["fsdp"]["param_bytes_per_device"]) < (
        multi["fsdp"]["param_tree_bytes"]
    )


def test_without_a_chip_the_smoke_fails_in_the_device_phase():
    out, lines = _smoke()
    assert out.returncode != 0
    assert [l["phase"] for l in lines] == ["device"]
    assert lines[-1]["ok"] is False

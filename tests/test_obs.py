"""The observability layer: honest timing, metrics, and the schema'd
receipt pipeline.

The load-bearing pins:

- :class:`MetricsLogger` performs NO host fetch on the step path — device
  scalars accumulate and drain in ONE batched ``jax.device_get`` at
  epoch/flush boundaries (none at all under ``defer_host_fetch`` until an
  explicit flush);
- every pre-schema receipt shape of rounds 1-5 passes retroactive legacy
  validation, and ``python -m ...obs --selftest`` (the end-to-end smoke)
  succeeds in a subprocess.
"""

import glob
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import optax
import pytest

from pytorch_distributed_training_tutorials_tpu.data import ShardedLoader, synthetic_regression
from pytorch_distributed_training_tutorials_tpu.models import LinearRegressor
from pytorch_distributed_training_tutorials_tpu.obs import (
    DriftBracket,
    MetricsLogger,
    MinOfN,
    launch_overhead_fit,
    load_receipt,
    make_receipt,
    validate_receipt,
    write_receipt,
)
from pytorch_distributed_training_tutorials_tpu.obs.timing import TimingResult
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
from pytorch_distributed_training_tutorials_tpu.train import Trainer

REPO = Path(__file__).resolve().parents[1]


# ------------------------------------------------------------- MetricsLogger

def test_metrics_logger_step_path_performs_no_host_fetch(monkeypatch):
    """The hot-path contract: log_step retains device scalars; ONE batched
    device_get happens at the epoch boundary, none before."""
    fetches = []
    real = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: fetches.append(1) or real(x)
    )
    m = MetricsLogger(quiet=True)
    import jax.numpy as jnp

    losses = [jnp.float32(i) for i in range(5)]
    for i, loss in enumerate(losses):
        m.log_step(i, loss)
    assert fetches == []  # five steps, zero syncs
    m.log_epoch({"epoch": 0, "loss": 0.5, "steps_per_sec": 2.0,
                 "samples_per_sec": 16.0})
    assert fetches == [1]  # the single batched drain
    steps = m.step_events()
    assert [e["step"] for e in steps] == list(range(5))
    assert [e["loss"] for e in steps] == [0.0, 1.0, 2.0, 3.0, 4.0]


def test_metrics_logger_defer_host_fetch_drains_only_on_flush(monkeypatch):
    fetches = []
    real = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: fetches.append(1) or real(x)
    )
    m = MetricsLogger(quiet=True, defer_host_fetch=True)
    import jax.numpy as jnp

    m.log_step(0, jnp.float32(1.5))
    m.log_epoch({"epoch": 0, "loss": 1.5, "steps_per_sec": 1.0,
                 "samples_per_sec": 8.0})
    assert fetches == []  # deferred: even the epoch boundary stays async
    assert m.step_events() == []  # pending, not yet events
    m.flush()  # THE explicit fetch point
    assert fetches == [1]
    assert m.step_events()[0]["loss"] == 1.5


def test_metrics_logger_verbose_step_prints_the_trainer_format(capsys):
    m = MetricsLogger()
    m.log_step(12, 1.23456, verbose=True)
    assert capsys.readouterr().out == "  step 12: loss 1.2346\n"
    # printed and recorded loss are the same fetched float
    m.flush()
    assert m.step_events()[0]["loss"] == pytest.approx(1.23456)


def test_metrics_logger_quiet_silences_console_not_events(capsys):
    m = MetricsLogger(quiet=True)
    m.log_step(1, 0.5, verbose=True)
    m.log_epoch({"epoch": 0, "loss": 0.5, "steps_per_sec": 1.0,
                 "samples_per_sec": 8.0})
    m.say("banner")
    assert capsys.readouterr().out == ""
    assert len(m.step_events()) == 1 and len(m.epoch_events()) == 1


def test_metrics_logger_epoch_line_format(capsys):
    m = MetricsLogger()
    m.log_epoch({"epoch": 3, "loss": 0.1234, "steps_per_sec": 12.34,
                 "samples_per_sec": 987.6})
    out = capsys.readouterr().out
    assert out == "  epoch 3: loss 0.1234 | 12.3 steps/s | 988 samples/s\n"


def test_metrics_logger_derives_tokens_per_sec_and_mfu():
    m = MetricsLogger(quiet=True, tokens_per_sample=4,
                      flops_per_token=10.0, peak_flops=100.0)
    ev = m.log_epoch({"epoch": 0, "loss": 1.0, "steps_per_sec": 2.0,
                      "samples_per_sec": 8.0})
    assert ev["tokens_per_sec"] == pytest.approx(32.0)
    assert ev["mfu"] == pytest.approx(3.2)
    assert m.last_epoch["mfu"] == pytest.approx(3.2)


def test_metrics_logger_jsonl_sink_mirrors_ring_buffer(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    with MetricsLogger(jsonl_path=path, quiet=True) as m:
        m.log_step(0, 2.0)
        m.log_epoch({"epoch": 0, "loss": 2.0, "steps_per_sec": 1.0,
                     "samples_per_sec": 8.0})
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    assert lines == list(m.events)
    assert [e["kind"] for e in lines] == ["step", "epoch"]


def test_metrics_logger_ring_buffer_caps_at_capacity():
    m = MetricsLogger(quiet=True, capacity=8)
    for i in range(32):
        m.log_step(i, float(i))
    m.flush()
    steps = m.step_events()
    assert len(steps) == 8
    assert steps[-1]["step"] == 31  # newest kept, oldest evicted


def test_trainer_routes_metrics_and_calls_hooks():
    """The Trainer integration: epoch metrics land in the logger, and the
    host-side on_step/on_epoch hooks fire without touching the jit."""
    mesh = create_mesh({"data": jax.device_count()})
    loader = ShardedLoader(
        synthetic_regression(size=64, in_dim=8, out_dim=1), 4, mesh
    )
    seen_steps, seen_epochs = [], []
    trainer = Trainer(
        LinearRegressor(in_dim=8), loader, optax.sgd(1e-2), loss="mse",
        quiet=True, on_step=lambda s, loss: seen_steps.append(s),
        on_epoch=lambda m: seen_epochs.append(m["epoch"]),
    )
    trainer.train(2)
    steps_per_epoch = len(loader)
    assert seen_steps[:steps_per_epoch] == list(range(1, steps_per_epoch + 1))
    assert seen_epochs == [0, 1]
    assert len(trainer.metrics.epoch_events()) == 2
    last = trainer.metrics.last_epoch
    assert last["epoch"] == 1 and "samples_per_sec" in last
    # un-verbose step losses drained at the epoch boundary, as floats
    assert all(
        isinstance(e["loss"], float) for e in trainer.metrics.step_events()
    )


# ------------------------------------------------------------------- timing

def test_min_of_n_runs_warmup_then_n_samples():
    calls = []
    timer = MinOfN(n=3, warmup=True)
    result = timer.measure(lambda: calls.append(1))
    assert len(calls) == 4  # 1 warmup + 3 timed
    assert len(result.samples_s) == 3
    assert result.best_s <= result.median_s
    assert MinOfN(n=2, warmup=False).measure(lambda: None).to_dict()["n"] == 2


def test_min_of_n_rejects_zero_samples():
    with pytest.raises(ValueError):
        MinOfN(n=0)


def test_timing_result_flags_stalls_instead_of_averaging_them():
    r = TimingResult(samples_s=[1.0, 1.1, 0.9, 10.0], stall_factor=5.0)
    assert r.best_s == 0.9
    assert r.n_stalled == 1 and r.stalled_s == [10.0]
    d = r.to_dict()
    assert d["n"] == 4 and d["n_stalled"] == 1
    # no stalls below the factor
    assert TimingResult(samples_s=[1.0, 1.2], stall_factor=5.0).n_stalled == 0


def test_drift_bracket_brackets_and_quantifies_the_window():
    legs = []
    bracket = DriftBracket(lambda: legs.append("ceiling"),
                           payload_bytes=10_000_000)
    out = bracket.around(lambda: legs.append("main") or 42)
    assert legs == ["ceiling", "main", "ceiling"]
    assert out.result == 42
    assert out.drift >= 1.0
    assert out.ceiling_s == min(out.before_s, out.after_s)
    d = out.to_dict()
    assert {"ceiling_before_s", "ceiling_after_s", "window_drift",
            "ceiling_mb_s"} <= set(d)
    # no payload -> no bandwidth claim
    assert "ceiling_mb_s" not in DriftBracket(lambda: None).around(
        lambda: None
    ).to_dict()


def test_launch_overhead_fit_separates_fixed_from_per_op():
    # synthetic runtime: 100 ms fixed launch + 1 ms per op
    fit = launch_overhead_fit(lambda n: 0.1 + n * 1e-3, lens=(64, 1024))
    assert fit.fixed_ms == pytest.approx(100.0, rel=1e-6)
    assert fit.per_op_us == pytest.approx(1000.0, rel=1e-6)
    # the misread this fit corrects: naively dividing a 32-chain reports
    # the roundtrip as if it were per-op time
    assert fit.naive_per_op_us(32) == pytest.approx(100e3 / 32 + 1000.0)
    assert fit.to_dict()["lens"] == [64, 1024]
    with pytest.raises(ValueError):
        launch_overhead_fit(lambda n: 0.1, lens=(64,))


# ------------------------------------------------------------------ receipts

def test_receipt_round_trip_with_env_stamp_and_drift(tmp_path):
    mesh = create_mesh({"data": jax.device_count()})
    path = str(tmp_path / "r.json")
    receipt = make_receipt(
        "bench_headline",
        {"metric": "img/s", "value": 123.0, "unit": "img/s"},
        mesh=mesh,
        drift={"window_drift": 1.1},
    )
    write_receipt(path, receipt)
    back = load_receipt(path)
    assert validate_receipt(back, kind="bench_headline") == []
    # flat merge: payload keys stay top-level (existing consumers)
    assert back["metric"] == "img/s" and back["value"] == 123.0
    assert back["schema"] == "graft-receipt/v1"
    env = back["env"]
    assert env["backend"] == "cpu" and env["device_count"] == 8
    assert env["jax_version"] == jax.__version__
    assert env["mesh"] == {"data": 8}
    assert back["drift"] == {"window_drift": 1.1}


def test_make_receipt_rejects_unknown_kind_and_envelope_collisions():
    with pytest.raises(ValueError, match="unknown receipt kind"):
        make_receipt("not_a_kind", {"x": 1})
    with pytest.raises(ValueError, match="collide"):
        make_receipt("serving", {"env": "oops"})


def test_validate_receipt_catches_broken_envelopes():
    good = make_receipt("serving", {"tok_s": 1.0})
    assert validate_receipt(good) == []
    assert validate_receipt(good, kind="bench_headline")  # kind mismatch
    assert validate_receipt({"schema": "graft-receipt/v1"})  # no kind/env
    assert validate_receipt("nope")  # not a dict
    bad_env = dict(good)
    bad_env["env"] = {"git_sha": None}
    assert any("jax_version" in p for p in validate_receipt(bad_env))
    empty = {k: good[k] for k in ("schema", "kind", "env")}
    assert any("empty payload" in p for p in validate_receipt(empty))


def test_write_receipt_refuses_invalid(tmp_path):
    with pytest.raises(ValueError, match="invalid receipt"):
        write_receipt(str(tmp_path / "x.json"),
                      {"schema": "graft-receipt/v1", "kind": "nope"})
    assert not (tmp_path / "x.json").exists()


def test_checked_in_bench_receipts_pass_retroactive_validation(tmp_path):
    """A pre-schema BENCH_r0*.json carries the metric/value/unit line
    under the min-of-N wrapper's "parsed" key — legacy mode validates
    that shape rather than grandfathering it in blind. (The five records
    of that shape the repo once held are gone; the validation they need
    stays in obs/receipt.py, so five of the same shape are written here.)"""
    for r in range(1, 6):
        (tmp_path / f"BENCH_r0{r}.json").write_text(json.dumps({
            "n": r, "cmd": "python bench.py", "rc": 0, "tail": "...",
            "parsed": {
                "metric": "images/sec/chip (ResNet-18 MNIST, end-to-end)",
                "value": 45000.0 + 1000.0 * r, "unit": "images/sec/chip",
                "n_chips": 1, "breakdown": {"h2d_window_drift": 1.1},
            },
        }))
    paths = sorted(glob.glob(str(tmp_path / "BENCH_r0*.json")))
    assert len(paths) >= 5, paths
    for p in paths:
        obj = load_receipt(p)
        assert validate_receipt(obj, kind="bench_headline") == [], p


# The records of rounds 4-5 that stated speeds of a runtime that is gone
# are deleted; what the legacy validator read of their shapes is kept, one
# place a number can sit a case (values are placeholders). The two records
# still at the root certify correctness on a CPU mesh.
_LEGACY_SHAPES = {
    "number_beside_strings": {"preset": "760m", "step_ms": 1.0},
    "number_beside_bools": {"scan_layers": True, "decode_tok_per_s": 1.0},
    "numbers_in_a_list": {"preset": "1b", "decode_s_samples": [1.0, 1.0]},
    "numbers_in_a_nested_dict": {
        "backend": "cpu", "prediction": {"assumed": True, "chips": 32},
    },
    "numbers_in_a_list_of_dicts": {
        "backend": "cpu", "points": [{"num_chips": 1, "step_time_s": 1.0}],
    },
}


@pytest.mark.parametrize(
    "name", [*_LEGACY_SHAPES, "MULTICHIP_r05.json", "ACCURACY_r04.json"]
)
def test_other_checked_in_receipts_validate_as_legacy(name, tmp_path):
    if name in _LEGACY_SHAPES:
        path = tmp_path / f"{name}.json"
        path.write_text(json.dumps(_LEGACY_SHAPES[name]))
    else:
        path = REPO / name
    assert validate_receipt(load_receipt(str(path))) == [], name


def test_pointer_files_are_not_mistaken_for_receipts():
    # BASELINE.json is config/pointers, not a measurement — legacy
    # validation refuses it rather than rubber-stamping any dict
    obj = load_receipt(str(REPO / "BASELINE.json"))
    assert any("no numeric measurement" in p for p in validate_receipt(obj))


# ------------------------------------------------------------- the selftest

def test_obs_selftest_subprocess(tmp_path):
    """``python -m ...obs --selftest`` — the end-to-end pipeline smoke
    (train with a JSONL logger, time a real chain, emit a validated
    receipt) — succeeds on the forced 8-device CPU mesh."""
    json_path = str(tmp_path / "selftest.json")
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tutorials_tpu.obs", "--selftest",
         "--json", json_path],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=os.environ.copy(),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    receipt = json.loads(out.stdout.strip().splitlines()[-1])
    assert receipt["ok"] is True, receipt.get("problems")
    assert validate_receipt(receipt, kind="obs_selftest") == []
    # the --json twin matches what stdout reported
    assert load_receipt(json_path)["ok"] is True

"""Trainer checkpoint/resume, evaluation, and profiler tracing."""

import pytest
import glob
import os

import numpy as np
import optax

from helpers import make_cls_dataset

from pytorch_distributed_training_tutorials_tpu.data import ShardedLoader
from pytorch_distributed_training_tutorials_tpu.models import MLP
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
from pytorch_distributed_training_tutorials_tpu.train import Trainer
from pytorch_distributed_training_tutorials_tpu.utils import profiling


def _trainer(seed=0):
    mesh = create_mesh({"data": 8})
    loader = ShardedLoader(make_cls_dataset(), 8, mesh, seed=0)
    return Trainer(
        MLP(features=(32, 4)), loader, optax.adam(1e-3),
        loss="cross_entropy", seed=seed,
    )


def test_save_restore_resume_bitwise_equals_straight_run(tmp_path):
    """train(4) == train(2) -> save -> fresh trainer -> restore -> train(4):
    identical params, proving step/opt-state/epoch all round-trip and the
    epoch-seeded reshuffle realigns."""
    straight = _trainer()
    straight.train(4)

    a = _trainer()
    a.train(2)
    ckpt = str(tmp_path / "ckpt")
    a.save(ckpt)

    b = _trainer(seed=123)  # different init — restore must overwrite it
    b.restore(ckpt)
    assert b.epoch == 2
    assert int(b.state.step) == int(a.state.step)
    b.train(4)  # continues epochs 2..3 only

    sp = straight.state.params
    bp = b.state.params
    for k in ("Dense_0", "Dense_1"):
        np.testing.assert_array_equal(
            np.asarray(sp[k]["kernel"]), np.asarray(bp[k]["kernel"])
        )


def test_restore_preserves_sharding(tmp_path):
    a = _trainer()
    a.train(1)
    ckpt = str(tmp_path / "ckpt")
    a.save(ckpt)
    b = _trainer()
    b.restore(ckpt)
    k = b.state.params["Dense_0"]["kernel"]
    # still replicated on all 8 devices (the DDP invariant)
    assert len(k.addressable_shards) == 8
    vals = [np.asarray(s.data) for s in k.addressable_shards]
    for v in vals[1:]:
        np.testing.assert_array_equal(vals[0], v)


def test_evaluate_reports_learning(tmp_path):
    t = _trainer()
    before = t.evaluate()
    t.train(5)
    after = t.evaluate()
    assert after["loss"] < before["loss"]
    assert after["accuracy"] > before["accuracy"]
    assert after["samples"] == 256


def test_evaluate_mse_regression():
    """evaluate() honors the trainer's configured loss (no CE on floats)."""
    from pytorch_distributed_training_tutorials_tpu.data import (
        synthetic_regression,
    )
    from pytorch_distributed_training_tutorials_tpu.models import LinearRegressor

    mesh = create_mesh({"data": 8})
    loader = ShardedLoader(synthetic_regression(256), 8, mesh)
    t = Trainer(LinearRegressor(), loader, optax.sgd(1e-2), loss="mse")
    before = t.evaluate()
    t.train(3)
    after = t.evaluate()
    assert after["loss"] < before["loss"]
    assert after["accuracy"] == 0.0  # undefined for regression


def test_train_skip_when_resumed_past_max_epochs(tmp_path):
    t = _trainer()
    t.train(2)
    out = t.train(2)  # already there
    assert out.get("skipped") is True
    assert np.isnan(out["loss"])


@pytest.mark.slow
def test_profiler_trace_produces_artifacts(tmp_path):
    logdir = str(tmp_path / "trace")
    t = _trainer()
    t.train(1)  # compile outside the trace
    with profiling.trace(logdir):
        with profiling.annotate("epoch"):
            t.train(2)
    files = glob.glob(os.path.join(logdir, "**", "*"), recursive=True)
    assert any("trace" in os.path.basename(f) for f in files), files


# ------------------------------------- atomic checkpoints + rollback (ISSUE 9)

def test_save_is_atomic_no_residue_and_overwrite(tmp_path):
    """save() lands via temp-dir + rename: after any completed save there
    is no .tmp/.old residue, and overwriting an existing checkpoint
    round-trips the NEW state (orbax's force=True delete-then-write
    window is closed by the swap)."""
    ck = str(tmp_path / "ck")
    a = _trainer()
    a.train(1)
    a.save(ck)
    assert os.path.isdir(ck)
    assert not os.path.exists(ck + ".tmp") and not os.path.exists(ck + ".old")
    a.train(2)
    a.save(ck)  # overwrite path: rename-swap, not delete-then-write
    assert os.path.isdir(ck)
    assert not os.path.exists(ck + ".tmp") and not os.path.exists(ck + ".old")
    b = _trainer(seed=9)
    b.restore(ck)
    assert b.epoch == 2
    assert int(b.state.step) == int(a.state.step)


def test_restore_falls_back_to_old_checkpoint(tmp_path):
    """The crash-window contract: if a save died between the two renames
    (only ``path.old`` exists), restore() uses it — at every instant one
    complete checkpoint is loadable."""
    ck = str(tmp_path / "ck")
    a = _trainer()
    a.train(2)
    a.save(ck)
    os.rename(ck, ck + ".old")  # simulate dying mid-swap
    b = _trainer(seed=9)
    b.restore(ck)
    assert b.epoch == 2
    assert int(b.state.step) == int(a.state.step)


def test_save_keep_rotation_and_newest_restore(tmp_path):
    """save(path, keep=K) rotates ``ckpt-{step:08d}`` children, pruning
    to the K newest; restore(path) on the directory resolves the newest
    child."""
    root = str(tmp_path / "rot")
    a = _trainer()
    a.train(1)
    a.save(root, keep=2)
    a.train(2)
    a.save(root, keep=2)
    a.train(3)
    a.save(root, keep=2)
    kids = sorted(
        d for d in os.listdir(root) if d.startswith("ckpt-")
    )
    assert len(kids) == 2
    assert kids[-1] == f"ckpt-{int(a.state.step):08d}"
    b = _trainer(seed=9)
    b.restore(root)  # newest child
    assert b.epoch == 3
    assert int(b.state.step) == int(a.state.step)


def test_loss_spike_rollback_restores_and_continues(tmp_path):
    """The ISSUE 9 rollback pin: a sustained (injected) loss spike past
    factor x EMA for `patience` consecutive observations restores the
    latest checkpoint and training CONTINUES — epoch position preserved
    (skip the bad region, don't replay it), exactly one rollback, and
    the run finishes with a finite loss."""
    from pytorch_distributed_training_tutorials_tpu.obs.flight import FlightRecorder
    from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

    ck = str(tmp_path / "ck")
    rec = FlightRecorder(capacity=64)
    t = Trainer(
        MLP(features=(32, 4)),
        ShardedLoader(make_cls_dataset(), 8, create_mesh({"data": 8}),
                      seed=0),
        optax.adam(1e-3), loss="cross_entropy", quiet=True,
        rollback_spike_factor=10.0, rollback_patience=2,
        chaos=ChaosConfig(spike_loss_step=6, spike_loss_len=3,
                          spike_loss_factor=1e6),
        flight=rec,
    )
    t.train(1)  # 4 steps/epoch: healthy monitor steps 1-4 seed the EMA
    t.save(ck)
    t.train(3)  # spike window hits monitor steps 6-8 -> strikes at 6,7
    assert t.rollbacks == 1
    assert t.epoch == 3  # continued to the end, no epoch replay
    assert np.isfinite(t.last_epoch_metrics["loss"])
    # ISSUE 10: the rollback stamped a fault-class flight event
    assert rec.kind_counts["rollback"] == 1 and rec.n_faults == 1
    (ev,) = [e for e in rec.events if e["kind"] == "rollback"]
    assert ev["step"] == 7 and ev["loss"] > 1e3


def test_rollback_without_checkpoint_raises():
    """Spiking with no prior save() is a hard error — silently training
    on from a corrupted state is the one thing rollback exists to
    prevent."""
    import pytest

    from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

    t = Trainer(
        MLP(features=(32, 4)),
        ShardedLoader(make_cls_dataset(), 8, create_mesh({"data": 8}),
                      seed=0),
        optax.adam(1e-3), loss="cross_entropy", quiet=True,
        rollback_spike_factor=10.0, rollback_patience=1,
        chaos=ChaosConfig(spike_loss_step=2, spike_loss_factor=1e6),
    )
    with pytest.raises(RuntimeError, match="no checkpoint"):
        t.train(1)

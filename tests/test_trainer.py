"""End-to-end SPMD training: the ddp_gpus.py workload on an 8-device mesh.

The v1 gate from SURVEY.md section 7: Linear(20,1) on the 2048-sample
synthetic dataset, data-parallel over all devices, loss decreases, and the
reference's observable semantics hold (steps math, replicated params, grad
sync equivalence to single-device large-batch training).
"""

import pytest
import jax
import jax.numpy as jnp
import numpy as np
import optax

from pytorch_distributed_training_tutorials_tpu.data import (
    ShardedLoader,
    synthetic_regression,
)
from pytorch_distributed_training_tutorials_tpu.models import LinearRegressor, MLP
from pytorch_distributed_training_tutorials_tpu.parallel import DataParallel
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
from pytorch_distributed_training_tutorials_tpu.train import Trainer


def _make_learnable_regression(n=2048, in_dim=20, seed=0):
    """y = x @ w + b + noise — learnable, unlike the reference's pure noise."""
    rng = np.random.Generator(np.random.PCG64(seed))
    x = rng.standard_normal((n, in_dim)).astype(np.float32)
    w = rng.standard_normal((in_dim, 1)).astype(np.float32)
    y = x @ w + 0.1 + 0.01 * rng.standard_normal((n, 1)).astype(np.float32)
    from pytorch_distributed_training_tutorials_tpu.data.datasets import ArrayDataset

    return ArrayDataset((x, y))


def test_ddp_gpus_workload_end_to_end():
    """The exact ddp_gpus.py shape: Linear(20,1), SGD(1e-2), bs 32/device."""
    mesh = create_mesh({"data": 8})
    ds = _make_learnable_regression()
    loader = ShardedLoader(ds, 32, mesh, shuffle=True)
    trainer = Trainer(
        LinearRegressor(), loader, optax.sgd(1e-2), loss="mse"
    )
    first = trainer._run_epoch(0)
    last = trainer.train(3)
    assert last["loss"] < first["loss"]
    assert last["steps"] == 8  # 2048 / 32 / 8 devices
    # params stayed replicated (the DDP invariant: all replicas identical)
    p = trainer.state.params["Dense_0"]["kernel"]
    shard_vals = [np.asarray(s.data) for s in p.addressable_shards]
    for sv in shard_vals[1:]:
        np.testing.assert_array_equal(shard_vals[0], sv)


def test_loss_decreases_mlp_classification():
    from helpers import make_cls_dataset

    mesh = create_mesh({"data": 8})
    loader = ShardedLoader(make_cls_dataset(n=1024), 16, mesh)
    trainer = Trainer(
        MLP(features=(64, 4)), loader, optax.adam(1e-3), loss="cross_entropy"
    )
    first = trainer._run_epoch(0)
    last = trainer.train(5)
    assert last["loss"] < first["loss"] * 0.5


def test_spmd_step_equals_single_device_large_batch():
    """Grad-allreduce correctness: one SPMD step over 8 shards == one
    single-device step on the concatenated batch (what DDP guarantees)."""
    from pytorch_distributed_training_tutorials_tpu.train.trainer import (
        create_train_state,
        make_train_step,
    )

    mesh = create_mesh({"data": 8})
    dp = DataParallel(mesh)
    model = LinearRegressor(in_dim=4)
    x = np.arange(8 * 2 * 4, dtype=np.float32).reshape(16, 4) / 100.0
    y = np.ones((16, 1), np.float32)

    state = create_train_state(model, optax.sgd(0.1), x, strategy=dp)
    step = make_train_step(loss="mse")
    new_state, m = step(state, (dp.shard_batch(x), dp.shard_batch(y)))

    # single-device run
    mesh1 = create_mesh({"data": 1}, devices=jax.devices()[:1])
    dp1 = DataParallel(mesh1)
    state1 = create_train_state(model, optax.sgd(0.1), x, strategy=dp1)
    step1 = make_train_step(loss="mse")
    new_state1, m1 = step1(state1, (dp1.shard_batch(x), dp1.shard_batch(y)))

    np.testing.assert_allclose(
        np.asarray(new_state.params["Dense_0"]["kernel"]),
        np.asarray(new_state1.params["Dense_0"]["kernel"]),
        rtol=1e-5,
    )
    np.testing.assert_allclose(float(m["loss"]), float(m1["loss"]), rtol=1e-5)


@pytest.mark.slow
def test_resnet_train_step_with_batch_stats():
    """BN models: batch_stats threads through the jitted step under sharding."""
    import optax

    from pytorch_distributed_training_tutorials_tpu.models import resnet18
    from pytorch_distributed_training_tutorials_tpu.data.datasets import ArrayDataset

    mesh = create_mesh({"data": 8})
    rng = np.random.Generator(np.random.PCG64(0))
    # 8x8 images: this test checks batch_stats plumbing (finite loss,
    # step count), not accuracy — XLA:CPU conv compile time dominates and
    # grows steeply with spatial size (see test_resident's measurements)
    x = rng.standard_normal((64, 8, 8, 3)).astype(np.float32)
    labels = rng.integers(0, 10, 64).astype(np.int32)
    loader = ShardedLoader(ArrayDataset((x, labels)), 4, mesh)
    trainer = Trainer(
        resnet18(num_classes=10, stem="cifar"),
        loader,
        optax.sgd(1e-2),
        loss="cross_entropy",
    )
    assert trainer.has_batch_stats
    m = trainer._run_epoch(0)
    assert np.isfinite(m["loss"])
    assert int(trainer.state.step) == 2  # 64 / 4 / 8


def test_evaluate_masks_wrap_padding():
    """Unbiased eval on a dataset that doesn't divide evenly: 100 samples on
    8 devices x bs 4 pads to 104 slots; masked eval must equal the plain
    single-device metrics over exactly the 100 unique samples (the
    reference's DistributedSampler would double-count the 4 duplicates)."""
    import optax
    from helpers import make_cls_dataset

    ds = make_cls_dataset(n=100, dim=16, classes=4)
    mesh = create_mesh({"data": 8})
    loader = ShardedLoader(ds, 4, mesh, shuffle=False)
    trainer = Trainer(
        MLP(features=(32, 4)), loader, optax.adam(1e-3), loss="cross_entropy"
    )
    m = trainer.evaluate()
    assert m["samples"] == 100  # not 104

    # single-device ground truth over the unique samples
    logits = trainer.state.apply_fn(
        {"params": jax.device_get(trainer.state.params)}, ds.arrays[0]
    )
    import optax as _optax

    ref_loss = float(
        _optax.softmax_cross_entropy_with_integer_labels(
            jnp.asarray(logits), jnp.asarray(ds.arrays[1])
        ).mean()
    )
    ref_acc = float(
        (np.argmax(np.asarray(logits), -1) == ds.arrays[1]).mean()
    )
    np.testing.assert_allclose(m["loss"], ref_loss, rtol=1e-5)
    np.testing.assert_allclose(m["accuracy"], ref_acc, rtol=1e-6)


def test_valid_mask_counts():
    """valid_mask marks exactly dataset-size slots real across the epoch."""
    from pytorch_distributed_training_tutorials_tpu.data.datasets import ArrayDataset

    ds = ArrayDataset((np.zeros((100, 4), np.float32),))
    mesh = create_mesh({"data": 8})
    loader = ShardedLoader(ds, 4, mesh, shuffle=True)
    total_real = sum(
        int(loader.valid_mask(s).sum()) for s in range(len(loader))
    )
    assert total_real == 100
    assert loader.valid_mask(0).shape == (32,)  # global batch, replica-major


def test_grad_accum_matches_full_batch():
    """grad_accum_steps=N inside the compiled step == one full-batch step
    (same mean gradient; BN stats averaged like tests/test_gpipe.py's rule)."""
    from pytorch_distributed_training_tutorials_tpu.train.trainer import (
        create_train_state,
        make_train_step,
    )

    mesh = create_mesh({"data": 8})
    dp = DataParallel(mesh)
    model = MLP(features=(32, 4))
    rng = np.random.Generator(np.random.PCG64(0))
    x = rng.standard_normal((32, 16)).astype(np.float32)
    y = rng.integers(0, 4, 32).astype(np.int32)
    batch = (dp.shard_batch(x), dp.shard_batch(y))

    def run(accum):
        import optax

        state = create_train_state(
            model, optax.sgd(0.1), x, strategy=dp, seed=0
        )
        step = make_train_step(loss="cross_entropy", grad_accum_steps=accum)
        state, m = step(state, batch)
        return float(m["loss"]), jax.device_get(state.params)

    loss1, params1 = run(1)
    loss4, params4 = run(4)
    np.testing.assert_allclose(loss1, loss4, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
        params1,
        params4,
    )


@pytest.mark.slow
def test_grad_accum_with_batch_stats_runs():
    from pytorch_distributed_training_tutorials_tpu.models import resnet18
    from pytorch_distributed_training_tutorials_tpu.train.trainer import (
        create_train_state,
        make_train_step,
    )
    import optax

    mesh = create_mesh({"data": 8})
    dp = DataParallel(mesh)
    model = resnet18(num_classes=10, stem="cifar")
    rng = np.random.Generator(np.random.PCG64(1))
    x = rng.standard_normal((32, 8, 8, 3)).astype(np.float32)
    y = rng.integers(0, 10, 32).astype(np.int32)
    state = create_train_state(model, optax.sgd(0.1), x, strategy=dp)
    step = make_train_step(
        loss="cross_entropy", has_batch_stats=True, grad_accum_steps=2
    )
    state, m = step(state, (dp.shard_batch(x), dp.shard_batch(y)))
    assert np.isfinite(float(m["loss"]))
    assert int(state.step) == 1


def test_trainer_grad_accum_param():
    """grad_accum_steps flows through the Trainer's documented surface."""
    import optax
    from helpers import make_cls_dataset

    mesh = create_mesh({"data": 8})
    loader = ShardedLoader(make_cls_dataset(n=256), 8, mesh)
    trainer = Trainer(
        MLP(features=(32, 4)), loader, optax.adam(1e-3),
        loss="cross_entropy", grad_accum_steps=2,
    )
    first = trainer._run_epoch(0)
    last = trainer.train(3)
    assert last["loss"] < first["loss"]


def test_scan_unroll_matches_unroll1():
    """scan_unroll is a scheduling knob only: the compiled epoch scan must
    produce bit-identical losses at any unroll factor (round-4 perf work —
    bench.py's step leg runs unroll=8)."""
    import optax
    from pytorch_distributed_training_tutorials_tpu.data import DeviceResidentLoader
    from helpers import make_cls_dataset

    mesh = create_mesh({"data": 8})
    ds = make_cls_dataset(n=128)
    losses = {}
    # 3 exercises the remainder path (4 steps % 3 != 0)
    for unroll in (1, 3):
        loader = DeviceResidentLoader(ds, 4, mesh, seed=0)
        trainer = Trainer(
            MLP(features=(16, 4)), loader, optax.sgd(0.1),
            loss="cross_entropy", scan_unroll=unroll,
        )
        m = trainer._run_epoch(0)
        losses[unroll] = m["loss"]
    # scheduling knob, not a numerics knob — but fusion boundaries may move,
    # so allow ulp-level drift rather than asserting bit-identity
    np.testing.assert_allclose(losses[1], losses[3], rtol=1e-6)


# ------------------------------------------------ skip-step guard (ISSUE 9)

def _guard_trainer(seed=0, **kw):
    """Linear regression on 8 steps/epoch — enough steps that a mid-epoch
    fault has healthy steps on both sides."""
    mesh = create_mesh({"data": 8})
    loader = ShardedLoader(
        _make_learnable_regression(), 32, mesh, seed=0
    )
    return Trainer(
        LinearRegressor(), loader, optax.adam(1e-2), loss="mse",
        seed=seed, quiet=True, **kw,
    )


def test_skip_step_elides_poisoned_update_and_continues():
    """The ISSUE 9 training acceptance pin: a run with one injected
    non-finite batch (host-keyed, fires exactly once) skips exactly that
    update and its final model is IDENTICAL to a clean run with the same
    update manually elided — training continues, nothing else changes."""
    from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

    leaves = jax.tree_util.tree_leaves
    t_guard = _guard_trainer(
        skip_nonfinite=True, chaos=ChaosConfig(nan_batch_step=3)
    )
    t_guard.train(1)
    assert t_guard.steps_skipped == 1
    assert int(t_guard.state.step) == 7  # 8 dispatches, 1 elided
    assert all(
        np.all(np.isfinite(np.asarray(l)))
        for l in leaves(t_guard.state.params)
    )
    # reference: the same epoch with update 3 manually elided
    t_ref = _guard_trainer()
    t_ref.loader.set_epoch(0)
    for i, batch in enumerate(t_ref.loader, start=1):
        if i == 3:
            continue
        t_ref.state, _ = t_ref.train_step(t_ref.state, batch)
    # float tolerance, not bitwise: the guarded step is a different
    # compiled program and XLA:CPU may fuse it to a different last ulp
    for la, lb in zip(
        leaves(t_guard.state.params), leaves(t_ref.state.params)
    ):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-7
        )


def test_skip_step_stamps_flight_event_on_batched_drain():
    """Trainer(flight=...) surfaces nonfinite skips as flight events
    THROUGH MetricsLogger's existing batched fetch (ISSUE 10) — the
    event exists after the epoch drain with the right step, and a
    no-fault guarded run stamps nothing."""
    from pytorch_distributed_training_tutorials_tpu.obs.flight import FlightRecorder
    from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

    rec = FlightRecorder(capacity=64)
    t = _guard_trainer(
        skip_nonfinite=True, chaos=ChaosConfig(nan_batch_step=3),
        flight=rec,
    )
    t.train(1)
    assert t.steps_skipped == 1
    assert rec.kind_counts["step_skipped"] == 1
    (ev,) = [e for e in rec.events if e["kind"] == "step_skipped"]
    assert ev["step"] == 3 and rec.n_faults == 1
    clean_rec = FlightRecorder(capacity=64)
    t_clean = _guard_trainer(skip_nonfinite=True, flight=clean_rec)
    t_clean.train(1)
    assert clean_rec.n_events == 0


def test_skip_step_guard_off_path_identical():
    """skip_nonfinite=True with NO faults changes nothing: params after a
    full epoch equal the guard-off trainer's (float tolerance: two
    compiled programs) and the skip counter stays zero."""
    leaves = jax.tree_util.tree_leaves
    t_a = _guard_trainer(skip_nonfinite=True)
    t_b = _guard_trainer()
    t_a.train(1)
    t_b.train(1)
    for la, lb in zip(
        leaves(t_a.state.params), leaves(t_b.state.params)
    ):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-7
        )
    assert t_a.steps_skipped == 0


def test_skip_step_state_bitwise_unchanged_on_poisoned_step():
    """Single-step bitwise pin, device-side grad poison: params,
    opt_state, AND step are unchanged through a poisoned update — the
    jnp.where select protects every leaf, including Adam moments."""
    from pytorch_distributed_training_tutorials_tpu.train.trainer import (
        create_train_state,
        make_train_step,
    )
    from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

    leaves = jax.tree_util.tree_leaves
    mesh = create_mesh({"data": 8})
    dp = DataParallel(mesh)
    model = LinearRegressor(in_dim=4)
    x = np.arange(32 * 4, dtype=np.float32).reshape(32, 4) / 100.0
    y = np.ones((32, 1), np.float32)
    state = create_train_state(model, optax.adam(1e-2), x[:8], strategy=dp)
    step = make_train_step(
        loss="mse", skip_nonfinite=True, chaos=ChaosConfig(nan_grad_step=0)
    )
    before = jax.device_get((state.params, state.opt_state, state.step))
    new_state, m = step(
        state, (dp.shard_batch(x), dp.shard_batch(y))
    )
    after = jax.device_get(
        (new_state.params, new_state.opt_state, new_state.step)
    )
    for a, b in zip(leaves(before), leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(jax.device_get(m["skipped"])) == 1


def test_skip_step_through_grad_accum_and_fused_adamw():
    """The guard composes with both optimizer paths ISSUE 9 names: a
    poisoned step through grad-accum microbatching and through
    fused_adamw's one-pass update leaves state bitwise unchanged (the
    where-select happens AFTER the fused update, on fresh buffers)."""
    from pytorch_distributed_training_tutorials_tpu.ops.fused_optim import fused_adamw
    from pytorch_distributed_training_tutorials_tpu.train.trainer import (
        create_train_state,
        make_train_step,
    )
    from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

    leaves = jax.tree_util.tree_leaves
    mesh = create_mesh({"data": 8})
    dp = DataParallel(mesh)
    model = LinearRegressor(in_dim=4)
    x = np.arange(32 * 4, dtype=np.float32).reshape(32, 4) / 100.0
    y = np.ones((32, 1), np.float32)
    for tx, accum in (
        (optax.adam(1e-2), 2),          # grad-accum path
        (fused_adamw(1e-2), 1),         # fused one-pass path
        (fused_adamw(1e-2), 2),         # both at once
    ):
        state = create_train_state(model, tx, x[:8], strategy=dp)
        step = make_train_step(
            loss="mse", grad_accum_steps=accum, skip_nonfinite=True,
            chaos=ChaosConfig(nan_grad_step=0),
        )
        before = jax.device_get((state.params, state.opt_state, state.step))
        new_state, m = step(
            state, (dp.shard_batch(x), dp.shard_batch(y))
        )
        after = jax.device_get(
            (new_state.params, new_state.opt_state, new_state.step)
        )
        for a, b in zip(leaves(before), leaves(after)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(jax.device_get(m["skipped"])) == 1

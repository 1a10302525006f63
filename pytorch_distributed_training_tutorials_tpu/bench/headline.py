"""The canonical headline-benchmark recipe, in one place.

``bench.py`` measures one program — the ResNet-18 bs512 bf16 MNIST
data-parallel train step (BASELINE.json's north star). This module owns
that setup (batch, transform, optimizer), apart from the timing legs.
"""

from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass
class HeadlineSetup:
    mesh: Any
    loader: Any          # DeviceResidentLoader over raw-uint8 MNIST
    trainer: Any
    batch: Any           # one transformed, device-ready cached batch
    step_fn: Any         # raw (unjitted) train step
    per_device_batch: int
    dataset: Any


def make_headline_setup(
    per_device_batch: int = 512, quiet: bool = False
) -> HeadlineSetup:
    """Build the headline workload: uint8-resident MNIST, bf16 cifar-stem
    ResNet-18, SGD+momentum trainer, plus a cached batch and the raw step
    function for chain-timing legs. ``quiet`` silences the trainer's epoch
    chatter (bench runs) without losing structured metrics."""
    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_distributed_training_tutorials_tpu.data import (
        DeviceResidentLoader,
        ShardedLoader,
        mnist,
    )
    from pytorch_distributed_training_tutorials_tpu.models import resnet18
    from pytorch_distributed_training_tutorials_tpu.parallel.mesh import (
        create_mesh,
    )
    from pytorch_distributed_training_tutorials_tpu.train import Trainer
    from pytorch_distributed_training_tutorials_tpu.train.trainer import (
        _train_step_fn,
    )

    mesh = create_mesh()
    ds = mnist("train", raw=True)
    loader = DeviceResidentLoader(
        ds, per_device_batch, mesh, seed=0,
        transform=lambda x, y: (x.astype(jnp.bfloat16) / 255.0, y),
    )
    model = resnet18(num_classes=10, stem="cifar", dtype=jnp.bfloat16)
    # scan_unroll=8 on the fused-epoch program: amortizes while-loop
    # bookkeeping over eight steps (its gain on today's chip: not
    # measured).
    trainer = Trainer(
        model, loader, optax.sgd(0.05, momentum=0.9),
        loss="cross_entropy", scan_unroll=8, quiet=quiet,
    )
    streaming = ShardedLoader(ds, per_device_batch, mesh, seed=0)
    batch = jax.block_until_ready(
        loader._apply_transform(next(iter(streaming)))
    )
    step_fn = _train_step_fn("cross_entropy", has_batch_stats=True)
    return HeadlineSetup(
        mesh=mesh,
        loader=loader,
        trainer=trainer,
        batch=batch,
        step_fn=step_fn,
        per_device_batch=per_device_batch,
        dataset=ds,
    )


def make_step_chain(setup: HeadlineSetup, chain_len: int, unroll: int = 8):
    """The jitted cached-batch step chain (one launch + one fetch) used by
    the ``train_step_only`` bench leg and the profiler."""
    import jax

    batch, step_fn = setup.batch, setup.step_fn

    def chain(state):
        def body(s, _):
            s, m = step_fn(s, batch)
            return s, m["loss"]

        return jax.lax.scan(body, state, None, length=chain_len, unroll=unroll)

    return jax.jit(chain)

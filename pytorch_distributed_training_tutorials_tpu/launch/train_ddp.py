"""Spawn-flavor DDP training CLI: twin of reference ``ddp_gpus.py``.

Same flag surface (``--max_epochs``, ``--batch_size`` with *per-device*
semantics, reference ``ddp_gpus.py:98-102``) and the same workload
(``Linear(20, 1)`` on the 2,048-sample synthetic dataset, SGD lr=1e-2,
``ddp_gpus.py:81-82``). The launch shape is TPU-native: on TPU hardware one
process drives all local chips (``--nprocs 1``, the default — SPMD replaces
per-device forking), while ``--nprocs N`` forks an N-process jax.distributed
world with explicit coordinator rendezvous — the exact ``mp.spawn`` contract
(rank injected, master address fixed up front, ``ddp_gpus.py:12-17,104-105``).

``--loss mse`` is the default: the reference calls ``F.cross_entropy`` on a
1-logit output with random float targets (``ddp_gpus.py:37``), which is
degenerate (constant zero gradient for soft targets over one class); MSE is
the regression loss its synthetic data implies. ``--loss cross_entropy``
restores the literal reference behavior.

Run::

    python -m pytorch_distributed_training_tutorials_tpu.launch.train_ddp \
        --max_epochs 10 --batch_size 32
    # hardware-free 4-process world (the reference's 4-GPU demo):
    python -m ... --nprocs 4 --platform cpu
"""

from __future__ import annotations

import argparse

import optax

from pytorch_distributed_training_tutorials_tpu.data import (
    ShardedLoader,
    synthetic_regression,
)
from pytorch_distributed_training_tutorials_tpu.models import LinearRegressor
from pytorch_distributed_training_tutorials_tpu.parallel import distributed
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
from pytorch_distributed_training_tutorials_tpu.train import Trainer

DATASET_SIZE = 2048  # reference ddp_gpus.py:72
LEARNING_RATE = 1e-2  # reference ddp_gpus.py:82


def main(
    rank: int,
    world_size: int,
    max_epochs: int,
    batch_size: int,
    coordinator: str | None = None,
    loss: str = "mse",
) -> None:
    """Per-process entry (twin of reference ``main``, ``ddp_gpus.py:69-93``).

    setup -> dataset -> sharded loader -> Linear(20,1) -> SGD -> Trainer ->
    train -> teardown, with the DDP wrap/allreduce replaced by SPMD sharding.
    """
    if world_size > 1:
        distributed.init(
            coordinator, num_processes=world_size, process_id=rank
        )
    mesh = create_mesh()  # {'data': all devices} — the world_size twin
    dataset = synthetic_regression(DATASET_SIZE)
    loader = ShardedLoader(dataset, batch_size, mesh)
    trainer = Trainer(
        LinearRegressor(), loader, optax.sgd(LEARNING_RATE), loss=loss
    )
    trainer.train(max_epochs)
    distributed.shutdown()


def build_parser(launch_flags: bool = True) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="TPU-native DDP training (spawn flavor)")
    # the reference's exact two flags (ddp_gpus.py:98-102)
    p.add_argument("--max_epochs", type=int, default=10,
                   help="Total epochs to train the model")
    p.add_argument("--batch_size", type=int, default=32,
                   help="Input batch size on each device (default: 32)")
    if launch_flags:
        p.add_argument("--nprocs", type=int, default=1,
                       help="Processes to fork (1 = pure SPMD over all "
                            "local chips; >1 = multi-process world, the "
                            "mp.spawn twin — needs --platform cpu: a chip "
                            "belongs to one process at a time)")
        p.add_argument("--platform", type=str, default=None,
                       help="Force a JAX platform in workers (e.g. 'cpu' for "
                            "the hardware-free multi-process harness)")
    p.add_argument("--loss", choices=("mse", "cross_entropy"), default="mse")
    return p


if __name__ == "__main__":
    args = build_parser().parse_args()
    if args.nprocs == 1:
        if args.platform is not None:
            # Backends aren't initialized yet (imports only trace modules),
            # so the config route still works here; mutating JAX_PLATFORMS
            # would be too late in this process.
            import jax

            jax.config.update("jax_platforms", args.platform)
        main(0, 1, args.max_epochs, args.batch_size, loss=args.loss)
    else:
        from pytorch_distributed_training_tutorials_tpu.launch import (
            coordinator_for_spawn,
            spawn,
        )

        coordinator = coordinator_for_spawn()
        spawn(
            main,
            args.nprocs,
            args=(args.nprocs, args.max_epochs, args.batch_size, coordinator,
                  args.loss),
            coordinator=coordinator,
            platform=args.platform,
        )

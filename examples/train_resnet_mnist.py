"""Flagship example: data-parallel ResNet-18 on MNIST (the BASELINE workload).

Runs on whatever is available — a TPU slice (`create_mesh()` takes every
chip), one chip, or a virtual CPU mesh for development:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_resnet_mnist.py --max_epochs 2

The reference's two flags keep their exact semantics (`--batch_size` is per
device, `ddp_gpus.py:101`); add `--fsdp` to shard params/optimizer over the
data axis instead of replicating (ZeRO-3), everything else unchanged.
"""

from __future__ import annotations

import argparse
import os
import sys

# runnable from a checkout without installation
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> None:
    from pytorch_distributed_training_tutorials_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--max_epochs", type=int, default=10)
    parser.add_argument(
        "--batch_size", type=int, default=32,
        help="Input batch size on each device (reference semantics)",
    )
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--fsdp", action="store_true",
                        help="shard params + optimizer state over data (ZeRO-3)")
    parser.add_argument("--ckpt", type=str, default=None,
                        help="checkpoint dir: resume if present, save per epoch")
    args = parser.parse_args()

    import jax.numpy as jnp
    import optax

    from pytorch_distributed_training_tutorials_tpu import create_mesh
    from pytorch_distributed_training_tutorials_tpu.data import DeviceResidentLoader, mnist
    from pytorch_distributed_training_tutorials_tpu.models import resnet18
    from pytorch_distributed_training_tutorials_tpu.parallel import FSDP
    from pytorch_distributed_training_tutorials_tpu.train import Trainer

    mesh = create_mesh()
    loader = DeviceResidentLoader(
        mnist("train", raw=True), args.batch_size, mesh, seed=0,
        transform=lambda x, y: (x.astype(jnp.bfloat16) / 255.0, y),
    )
    trainer = Trainer(
        resnet18(num_classes=10, stem="cifar", dtype=jnp.bfloat16),
        loader,
        optax.sgd(args.lr, momentum=0.9),
        strategy=FSDP(mesh) if args.fsdp else None,
        loss="cross_entropy",
    )
    if args.ckpt and os.path.exists(args.ckpt):
        trainer.restore(args.ckpt)
        print(f"resumed at epoch {trainer.epoch}")
    while trainer.epoch < args.max_epochs:
        trainer.train(trainer.epoch + 1)
        if args.ckpt:
            trainer.save(args.ckpt)

    test = DeviceResidentLoader(
        mnist("test", raw=True), args.batch_size, mesh, seed=0,
        transform=loader.transform,
    )
    print("eval:", trainer.evaluate(test))


if __name__ == "__main__":
    main()

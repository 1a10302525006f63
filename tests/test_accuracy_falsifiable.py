"""The accuracy demonstration must be falsifiable (round-3 verdict task 4).

Round 3's surrogate saturated at ``eval_accuracy 1.0 / eval_loss 0.0`` —
``reaches_accuracy_target`` was a tautology a real training regression could
pass. The hardened surrogate (``_synthetic_images``: multi-modal class
manifolds at signal=0.35) makes the metric mean something; these tests pin
both directions on a fast CPU proxy (small MLP, data subset):

- healthy training separates the classes far above chance with nonzero loss
- a deliberately broken config (diverged learning rate) FAILS the check —
  the negative control the round-2/round-3 verdicts asked for

The full-scale positive result (ResNet-18, 7 bench epochs -> 0.9961 with
eval_loss 0.0132; signal=0.30 misses at 0.9867) is recorded in the
``_synthetic_images`` docstring.
"""

import jax.numpy as jnp
import numpy as np
import optax

from pytorch_distributed_training_tutorials_tpu.data import DeviceResidentLoader
from pytorch_distributed_training_tutorials_tpu.data.datasets import (
    _synthetic_images,
)
from pytorch_distributed_training_tutorials_tpu.models import MLP
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
from pytorch_distributed_training_tutorials_tpu.train import Trainer


def _flatten(x, y):
    return x.reshape(x.shape[0], -1).astype(jnp.float32) / 255.0, y


_CACHE: dict = {}


def _train_and_eval(lr: float, epochs: int = 4):
    if (lr, epochs) in _CACHE:  # both tests use the healthy run
        return _CACHE[(lr, epochs)]
    mesh = create_mesh({"data": 8})
    train = _synthetic_images(4096, (28, 28, 1), 10, 101, 1, raw=True)
    test = _synthetic_images(1024, (28, 28, 1), 10, 101, 2, raw=True)
    loader = DeviceResidentLoader(
        train, 64, mesh, seed=0, transform=_flatten
    )
    trainer = Trainer(
        MLP(features=(128, 10)), loader,
        optax.sgd(lr, momentum=0.9), loss="cross_entropy",
    )
    trainer.train(epochs)
    m = trainer.evaluate(
        DeviceResidentLoader(test, 64, mesh, seed=0, transform=_flatten)
    )
    _CACHE[(lr, epochs)] = m
    return m


def test_healthy_training_learns_with_nonzero_loss():
    m = _train_and_eval(lr=0.05)
    # the CPU proxy (small MLP, 4k samples) doesn't hit the full-scale 0.99,
    # but it must separate the manifolds far above chance...
    assert m["accuracy"] > 0.7, m
    # ...and the hardened surrogate must NOT saturate to the vacuous
    # loss==0.0 that made round 3's demonstration untestable
    assert m["loss"] > 1e-3, m


def test_broken_config_fails_the_target():
    """lr=10 diverges: the accuracy target must be missed — the negative
    control that makes `reaches_accuracy_target` informative."""
    m = _train_and_eval(lr=10.0)
    healthy = _train_and_eval(lr=0.05)
    accuracy_target = 0.99  # bench.py's target
    assert m["accuracy"] < accuracy_target
    # and not by a hair: a diverged run sits near chance, far under healthy
    assert m["accuracy"] < 0.5 < healthy["accuracy"]
    assert m["accuracy"] + 0.2 < healthy["accuracy"]

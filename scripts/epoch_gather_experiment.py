"""Compare epoch-scan input strategies on the headline workload (real chip).

Two candidates for feeding the whole-epoch ``lax.scan``
(``train.trainer.make_epoch_scan`` uses A today):

  A. per-step gather: scan body does ``data[idx_step]`` (512 random rows,
     ~0.4 MB uint8) and fuses the transform into the step;
  B. pre-gathered epoch: ONE ``data[idx]`` gather materializes the epoch as
     ``(steps, B, ...)`` (~47 MB uint8 for MNIST) before the scan, whose
     body then consumes contiguous slices (XLA scan indexing, no gather).

Usage: python scripts/epoch_gather_experiment.py [per_device_batch] [unroll]
Prints one JSON line with img/s for both variants, min-of-3 (a shared
host can stall an individual dispatch; first fetch primed by compile leg).

RESULT (round 4, v5e lite, bs512): per_step_gather 45,294 img/s vs
pregather 44,611 — the one-big-gather variant is ~1.5% SLOWER. The
per-step gather fuses into the step's first convolution and is not a
bottleneck; B stays the trainer's layout.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tutorials_tpu.bench.headline import (
        make_headline_setup,
    )
    from pytorch_distributed_training_tutorials_tpu.train.trainer import (
        _train_step_fn,
    )

    per_device_batch = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    unroll = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    setup = make_headline_setup(per_device_batch)
    loader, trainer = setup.loader, setup.trainer
    data = loader.device_arrays
    idx = loader.epoch_index_array(0)
    steps = int(idx.shape[0])
    step_fn = _train_step_fn("cross_entropy", has_batch_stats=True)

    def transform(x, y):
        return (x.astype(jnp.bfloat16) / 255.0, y)

    @functools.partial(jax.jit, donate_argnums=0)
    def epoch_per_step_gather(state, idx):
        def body(state, idx_step):
            batch = transform(*(a[idx_step] for a in data))
            state, m = step_fn(state, batch)
            return state, m["loss"]

        return jax.lax.scan(body, state, idx, unroll=unroll)

    @functools.partial(jax.jit, donate_argnums=0)
    def epoch_pregather(state, idx):
        epoch = tuple(a[idx] for a in data)  # (steps, B, ...) uint8/int32
        def body(state, batch):
            state, m = step_fn(state, transform(*batch))
            return state, m["loss"]

        return jax.lax.scan(body, state, epoch, unroll=unroll)

    results = {"per_device_batch": per_device_batch, "unroll": unroll,
               "steps": steps}
    for name, fn in [("per_step_gather", epoch_per_step_gather),
                     ("pregather", epoch_pregather)]:
        # fresh buffer copy per variant: both variants donate their state
        state0 = jax.tree_util.tree_map(jnp.asarray, trainer.state)
        state0 = jax.tree_util.tree_map(jnp.copy, state0)
        state, losses = fn(state0, idx)  # compile + prime first fetch
        float(losses[-1])
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            state, losses = fn(state, idx)
            float(losses[-1])
            best = min(best, time.perf_counter() - t0)
        results[name + "_images_per_sec"] = round(
            steps * setup.per_device_batch / best, 1
        )
    print(json.dumps(results))


if __name__ == "__main__":
    main()

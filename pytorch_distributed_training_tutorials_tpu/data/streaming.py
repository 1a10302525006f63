"""Chunked streaming: amortize per-batch H2D latency over many steps.

The per-step streaming path pays a full host->device round trip for each
512-row batch, a fixed cost on top of the 400 KB transfer itself (its
size on the chip: not measured). The reference hides the same latency with
worker processes + ``pin_memory`` (``/root/reference/ddp_gpus.py:73-79``);
the TPU-idiomatic equivalent restructures the transfer, not just the
scheduling:

1. **chunking** — gather ``steps_per_chunk`` steps' rows at once and ship
   them as ONE sharded ``(steps, global_batch, ...)`` array: one H2D
   enqueue per chunk instead of per step, so the fixed dispatch/roundtrip
   cost divides by the chunk length;
2. **prefetch** — the next chunk's gather + H2D runs in a background
   thread (:func:`.prefetch.prefetch_iterable`) while the device trains on
   the current one;
3. **scanned consumption** — the Trainer runs each chunk as one jitted
   ``lax.scan`` of train steps (``Trainer._run_epoch_chunked``), so launch
   overhead amortizes the same way the device-resident epoch scan does.

Together the streaming path approaches the device-resident one while
holding only ``prefetch * steps_per_chunk`` batches in HBM — the input
pipeline for datasets that do NOT fit on device.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from pytorch_distributed_training_tutorials_tpu.data.datasets import ArrayDataset
from pytorch_distributed_training_tutorials_tpu.data.loader import ShardedLoader
from pytorch_distributed_training_tutorials_tpu.data.native import gather_rows
from pytorch_distributed_training_tutorials_tpu.data.prefetch import (
    prefetch_iterable,
)


class ChunkedStreamingLoader(ShardedLoader):
    """A :class:`ShardedLoader` that also serves whole multi-step chunks.

    Per-step iteration (``__iter__``) keeps the parent's semantics, so
    everything written against ``ShardedLoader`` still works; consumers
    that know about :meth:`iter_chunks` (``Trainer``) stream
    ``(steps_per_chunk, global_batch, ...)`` arrays — dim 1 sharded over
    the data axis, dim 0 the scan axis — with the next chunk prefetched in
    the background.

    ``transform`` runs inside the consumer's compiled scan (the Trainer
    threads ``self.transform`` into its chunk-scan body), exactly like the
    device-resident epoch scan.
    """

    def __init__(
        self,
        dataset: ArrayDataset,
        batch_size: int,
        mesh: Mesh,
        *,
        steps_per_chunk: int = 16,
        prefetch: int = 2,
        transform=None,
        **kwargs,
    ):
        if kwargs.get("batch_spec") is not None:
            raise NotImplementedError(
                "ChunkedStreamingLoader shards batches over the data axis "
                "only; use ShardedLoader for custom batch_specs"
            )
        if steps_per_chunk < 1:
            raise ValueError("steps_per_chunk must be >= 1")
        super().__init__(
            dataset, batch_size, mesh, transform=transform, **kwargs
        )
        self.steps_per_chunk = steps_per_chunk
        self.prefetch = prefetch
        # (steps, rows, ...): rows over the data axis, steps unsharded
        self._chunk_shardings = [
            NamedSharding(mesh, PartitionSpec(None, self.axis))
            for _ in dataset.arrays
        ]

    def _make_chunk(self, step_rows: np.ndarray):
        """One chunk: ``step_rows`` is (c, global_batch) dataset indices in
        replica-major per-step order. Returns a tuple of sharded
        ``(c, global_batch, ...)`` arrays; the per-device callback gathers
        only that device's rows (for all c steps) in one native gather."""
        c = step_rows.shape[0]

        def make(ai: int):
            arr = self.dataset.arrays[ai]
            gshape = (c, self.global_batch, *arr.shape[1:])

            def cb(index):
                rows = step_rows[:, index[1]]  # (c, rows_per_device)
                flat = gather_rows(arr, rows.reshape(-1))
                return flat.reshape(c, -1, *arr.shape[1:])

            return jax.make_array_from_callback(
                gshape, self._chunk_shardings[ai], cb
            )

        return tuple(make(ai) for ai in range(len(self.dataset.arrays)))

    def iter_chunks(self):
        """Yield the epoch as prefetched multi-step chunks (the last chunk
        may be shorter — at most two distinct scan lengths compile)."""
        shards = self._epoch_index_matrix()  # (world, steps * bs)
        bs = self.per_device_batch
        idx = (
            shards.reshape(self.world, self.steps_per_epoch, bs)
            .transpose(1, 0, 2)
            .reshape(self.steps_per_epoch, self.global_batch)
        )

        def chunks():
            for lo in range(0, self.steps_per_epoch, self.steps_per_chunk):
                yield self._make_chunk(
                    idx[lo : lo + self.steps_per_chunk]
                )

        return prefetch_iterable(chunks(), self.prefetch)

"""Model utilities."""

from __future__ import annotations

import jax


def model_size(params) -> int:
    """Total parameter count of a pytree of arrays.

    Twin of the reference's ``sum(p.numel() for p in model.parameters())``
    (``03.model_parallel.ipynb:844-848``), which reports 25,557,032 for
    ResNet-50 — invariant under any split, since sharding annotations don't
    change the tree. Counts *parameters* only; pass the ``params`` collection,
    not ``batch_stats`` (torch's ``parameters()`` likewise excludes buffers).
    """
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


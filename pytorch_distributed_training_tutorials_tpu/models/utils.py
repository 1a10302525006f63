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


def model_flops_per_token(n_params_nonembed: int, d_model: int,
                          n_layers: int, seq_len: int) -> float:
    """Training FLOPs per token, PaLM appendix-B convention: 6x the
    non-embedding params (fwd 2x + bwd 4x) plus ``12*L*d*S`` for the two
    attention einsums (QK^T and weights@V, fwd+bwd; no causality
    discount). Remat recompute does NOT count, so remat honestly lowers
    MFU unless it buys a bigger batch.

    This analytic count is the one MFU numerator in the repo
    (bench.lm_headline, scripts/train_llm_mfu.py): XLA's
    ``compiled.cost_analysis()['flops']`` counts a ``lax.scan``/``while``
    body ONCE, not times its trip count, so it under-reports a
    ``scan_layers`` model by ~n_layers x (measured: 5.4 TF "executed" vs
    52.8 TF analytic on the 24-layer 350m step — round 5).
    Exclude ``tok_emb`` (a gather, not a matmul) from
    ``n_params_nonembed`` but keep ``lm_head`` (it IS a matmul — and
    stays one inside the fused blockwise loss).
    """
    return 6.0 * n_params_nonembed + 12.0 * n_layers * d_model * seq_len

"""Ulysses-style all-to-all sequence parallelism over the ``seq`` mesh axis.

The second of the two canonical long-context shardings (the task's
"ring attention or all-to-all sequence/context parallelism"):

- **Ring** (:mod:`.ring_attention`): K/V blocks rotate; each device computes
  its queries against every block with an online softmax. Communication is
  ``n-1`` neighbor ``ppermute`` hops of the K/V blocks — bandwidth scales
  with sequence length, ideal on an ICI torus, and score memory is
  O((S/n)^2).
- **All-to-all (Ulysses)**: one ``all_to_all`` redistributes from
  sequence-sharded activations to *head*-sharded ones, every device runs
  ordinary full-sequence attention on ``H/n`` local heads, and a second
  ``all_to_all`` redistributes back. Communication is two all-to-alls of the
  activations (cheaper than a ring when heads are plentiful and the mesh has
  good bisection bandwidth); score memory is O(S^2 / n) spread over heads.

The two are numerically interchangeable with dense causal attention and are
drop-ins for each other via ``TransformerConfig.attention_fn``; which wins is
a topology question (ring for long S on a torus, Ulysses for many-head
models on meshes with fast all-to-all), so the framework ships both.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import shard_map
from jax.sharding import Mesh

from pytorch_distributed_training_tutorials_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
)
from pytorch_distributed_training_tutorials_tpu.parallel.ring_attention import (
    _qkv_spec,
)


def make_ulysses_attention(
    mesh: Mesh,
    *,
    seq_axis: str = SEQ_AXIS,
    data_axis: str = DATA_AXIS,
    model_axis: str = MODEL_AXIS,
    inner_attention=None,
):
    """Build a causal ``attention_fn(q, k, v) -> out`` ((B, S, H, D) each)
    computing attention sequence-parallel via head redistribution.

    ``inner_attention`` is the per-device full-sequence attention (default:
    the dense causal softmax attention the transformer uses unsharded), so
    Ulysses composes with any local attention kernel. Requires the ``seq``
    axis size to divide the (per-device) head count — each device must own
    a whole head group after the redistribution.

    Numerical equivalence to dense attention and to the ring is pinned in
    ``tests/test_ulysses.py``.
    """
    if seq_axis not in mesh.shape:
        raise ValueError(f"mesh has no {seq_axis!r} axis: {dict(mesh.shape)}")
    n = mesh.shape[seq_axis]
    spec = _qkv_spec(mesh, data_axis, seq_axis, model_axis)

    if inner_attention is None:
        from pytorch_distributed_training_tutorials_tpu.models.transformer import (
            causal_attention,
        )

        inner_attention = causal_attention

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    def ulysses_attention(qb, kb, vb):
        h = qb.shape[2]
        if h % n:
            raise ValueError(
                f"Ulysses needs heads ({h} local) divisible by the "
                f"{seq_axis!r} axis ({n})"
            )
        # (B, S/n, H, D) -> (B, S, H/n, D): trade the sequence shard for a
        # head shard in ONE collective
        q, k, v = (
            jax.lax.all_to_all(
                x, seq_axis, split_axis=2, concat_axis=1, tiled=True
            )
            for x in (qb, kb, vb)
        )
        # full-sequence causal attention on the local head group — global
        # positions need no bookkeeping because S is whole here
        out = inner_attention(q, k, v)
        # (B, S, H/n, D) -> (B, S/n, H, D)
        return jax.lax.all_to_all(
            out, seq_axis, split_axis=1, concat_axis=2, tiled=True
        )

    # generate()'s prefill checks this: Ulysses needs S to divide the seq
    # axis, so non-divisible prompt lengths prefill via the dense path
    ulysses_attention.requires_seq_divisible = n
    return ulysses_attention

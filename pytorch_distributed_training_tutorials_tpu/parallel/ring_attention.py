"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

First-class long-context capability (the reference has *no* attention at all
in repo-authored code — SURVEY.md section 5.7 — so this is beyond-parity by
design; the mesh reserved the ``seq`` axis for it from day one). The design is
the TPU-native ring: every device holds one sequence block of Q/K/V; K/V
blocks rotate around the ring with ``lax.ppermute`` over ICI while each
device folds the incoming block into its queries' attention state with the
numerically-stable online-softmax update (running max ``m``, normalizer
``l``, unnormalized accumulator ``o`` — the blockwise/flash decomposition).
Peak memory per device is O(S/n * S/n) scores instead of O(S^2): sequence
length scales linearly with the ring size. The bound holds through
**backward** too: each hop is ``jax.checkpoint``-ed (see :func:`_ring_hop`),
so ``jax.grad`` re-derives score blocks instead of storing one per hop.

The ring is unrolled (ring size is a static mesh property), so XLA can
overlap each step's ppermute with the previous step's matmuls — communication
hides behind compute exactly like the NCCL bucket overlap the reference's DDP
relies on, but compiled rather than hand-scheduled.

Composes with the other axes: batch stays sharded on ``data``, heads on
``model`` (heads are independent in attention, so tensor parallelism passes
straight through), sequence on ``seq``. Plug the returned function into
:class:`..models.transformer.TransformerConfig` via ``attention_fn``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from pytorch_distributed_training_tutorials_tpu.utils.compat import (
    shard_map_nocheck,
)
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
)

# plain float, NOT jnp.float32(...): creating a jax array at import time
# would initialize the XLA backend, which breaks multi-process workers that
# must call jax.distributed.initialize() before any JAX computation
NEG_INF = float("-inf")


def _qkv_spec(mesh: Mesh, data_axis: str, seq_axis: str, model_axis: str) -> P:
    """(B, S, H, D) spec using only the axes the mesh actually has."""
    has = mesh.shape
    return P(
        data_axis if data_axis in has else None,
        seq_axis if seq_axis in has else None,
        model_axis if model_axis in has else None,
        None,
    )


def _fold_block(carry, xs, qb, q_pos, scale):
    """Fold ONE key sub-block into the online-softmax state — the flash-
    attention inner body, shared by every hop."""
    o, l, m = carry
    kb, vb, k_pos = xs
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", qb, kb, preferred_element_type=jnp.float32
    ) * scale
    causal = q_pos[:, None] >= k_pos[None, :]  # (s_blk, blk) global
    scores = jnp.where(causal[None, None], scores, NEG_INF)

    m_new = jnp.maximum(m, scores.max(axis=-1))
    # m_new is finite from t=0 on: src==idx at t=0, so every query row sees
    # its own diagonal key first. (If the rotation start is ever changed,
    # -inf rows would need exp-of-nan guards here.)
    # (at t=0, corr = exp(-inf - finite) = 0 exactly, zeroing the empty
    # initial accumulators — no NaN guard needed)
    p = jnp.exp(scores - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    o = o * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32)
    )
    return (o, l, m_new), None


@partial(jax.checkpoint, static_argnums=(9,))
def _ring_hop(qb, k_t, v_t, o, l, m, q_pos, k_pos, scale, block=512):
    """One ring hop: fold an incoming K/V block into the online-softmax
    state ``(o, l, m)`` — itself BLOCKWISE (the flash decomposition), so
    even the per-hop score tile is (s_blk, block), not (s_blk, s_blk).

    Two memory properties compose here:

    - ``jax.checkpoint`` on the hop makes the module's O((S/n)^2)-or-
      better claim true *through backward*: without it, ``jax.grad`` over
      the unrolled ring stores every hop's probability blocks — n of
      them, i.e. O(S^2/n) per device, roughly the thing the ring exists
      to avoid (``tests/test_ring_attention.py`` pins the residual
      footprint vs dense attention).
    - the inner ``lax.scan`` over ``block``-sized key sub-blocks (each
      fold itself checkpointed) bounds LIVE memory to O(s_blk * block)
      per device in forward and in the hop's rematerialized backward —
      the same blockwise-online-softmax structure as the single-chip
      Pallas kernel (``ops/flash_attention.py``), here as compiler-
      friendly scanned jnp so XLA can still overlap the ring ppermute
      with compute.
    """
    s_blk = k_t.shape[1]
    block = min(block, s_blk)
    if s_blk % block:
        # ragged tails fall back to one fold over the whole hop block
        block = s_blk
    nb = s_blk // block

    def to_blocks(a):  # (b, s_blk, h, d) -> (nb, b, block, h, d)
        return a.reshape(
            a.shape[0], nb, block, *a.shape[2:]
        ).swapaxes(0, 1)

    xs = (to_blocks(k_t), to_blocks(v_t), k_pos.reshape(nb, block))
    fold = jax.checkpoint(
        lambda c, x: _fold_block(c, x, qb, q_pos, scale),
        prevent_cse=False,
    )
    (o, l, m), _ = jax.lax.scan(fold, (o, l, m), xs)
    return o, l, m


def make_ring_attention(
    mesh: Mesh,
    *,
    seq_axis: str = SEQ_AXIS,
    data_axis: str = DATA_AXIS,
    model_axis: str = MODEL_AXIS,
    hop_block: int = 512,
):
    """Build a causal ``attention_fn(q, k, v) -> out`` ((B, S, H, D) each)
    that computes attention sequence-parallel over ``mesh[seq_axis]``.

    Numerically equivalent to :func:`..models.transformer.causal_attention`
    (verified to float tolerance in ``tests/test_ring_attention.py``); the
    difference is where the bytes live: no device ever materializes the full
    (S, S) score matrix or the full K/V. ``hop_block`` bounds the per-hop
    score tile (see :func:`_ring_hop`): live score memory is
    O(s_blk * hop_block) per device, forward and backward.
    """
    if seq_axis not in mesh.shape:
        raise ValueError(f"mesh has no {seq_axis!r} axis: {dict(mesh.shape)}")
    n = mesh.shape[seq_axis]
    spec = _qkv_spec(mesh, data_axis, seq_axis, model_axis)

    # checking off: the ring is an unrolled ppermute chain the checker
    # cannot follow; the fresh (o, l, m) scan carry is tagged varying
    # below so it matches the ppermute-fed fold outputs
    @partial(
        shard_map_nocheck,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    def ring_attention(qb: jax.Array, kb: jax.Array, vb: jax.Array) -> jax.Array:
        b, s_blk, h, d = qb.shape
        idx = jax.lax.axis_index(seq_axis)
        q_pos = idx * s_blk + jnp.arange(s_blk)  # global positions of my queries

        scale = 1.0 / jnp.sqrt(jnp.float32(d))
        o = jnp.zeros((b, h, s_blk, d), jnp.float32)
        l = jnp.zeros((b, h, s_blk), jnp.float32)
        # strong f32 (a weak-typed full() would flip type across the
        # blockwise scan carry)
        m = jnp.full((b, h, s_blk), NEG_INF, jnp.float32)
        # the hop's inner scan requires carry types stable across
        # iterations, including the varying-manual-axis tags the folded
        # (sharded) K/V blocks impart — mark the fresh state varying over
        # every mesh axis up front (the fold output's tag is the union of
        # the carry's and the sharded operands').
        o, l, m = jax.lax.pcast(
            (o, l, m), tuple(mesh.axis_names), to="varying"
        )

        k_t, v_t = kb, vb
        shift = [(j, (j + 1) % n) for j in range(n)]
        for t in range(n):  # static ring, unrolled for ppermute/compute overlap
            # after t hops I hold the block that started on device (idx - t)
            src = (idx - t) % n
            k_pos = src * s_blk + jnp.arange(s_blk)
            o, l, m = _ring_hop(
                qb, k_t, v_t, o, l, m, q_pos, k_pos, scale, hop_block
            )
            if t < n - 1:
                k_t, v_t = jax.lax.ppermute(
                    (k_t, v_t), seq_axis, perm=shift
                )

        # causal => every query row saw at least its own diagonal block
        out = o / l[..., None]
        return out.transpose(0, 2, 1, 3).astype(qb.dtype)

    # generate()'s prefill checks this: ring needs S to divide the seq
    # axis, so non-divisible prompt lengths prefill via the dense path
    # (divisible ones keep the ring and its memory bound)
    ring_attention.requires_seq_divisible = n
    return ring_attention

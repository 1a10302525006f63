"""Decode attention over a latent cache, read where it lies.

Latent attention (``LatentAttention`` in ``models/transformer.py``) caches one
row ``[c | k_rope | 0...]`` a token and a layer, shared by all heads, and
at decode absorbs the up-projection into the query, so that a head's
scores are its ``(rank + rope)``-wide query against the rows themselves
and its output is the softmax-weighted sum of the rows (the first ``rank``
numbers of it, times ``W_uv``, outside). Written as plain einsums that is
two passes over a layer's whole window, behind a copy of the layer's
slice out of the stack the layer scan carries, with the float32 scores of
every head written out between them: at 128 slots x 4,096 positions some
3 GB of traffic a layer for 0.7 GB of cache, most of it past the rows'
own depths.

This kernel walks ``(slot, block of positions)`` with the layer index and
the slots' depths as **scalar-prefetch** operands: the cache block's index
map points into the carried stack ``(L, B, W, C)`` at ``[layer, slot]``
(no slice is copied), blocks past a slot's depth are neither fetched nor
computed, a slot whose depth is the window holds nothing and costs no rows
(:func:`.decode_attention.block_bounds`, as for heads of K and V), every
head reads the one block (the heads are the rows of one matrix product),
and the softmax runs as the streaming ``(m, l, acc)`` recurrence of
:mod:`.paged_attention`. One query position a slot (the
serving chain's step, ``generate()``'s step); a chunk of several goes
through the plain einsums, which are also this kernel's numerics
reference (:func:`latent_decode_attention_reference`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_training_tutorials_tpu.ops.decode_attention import (
    block_bounds,
)

NEG_INF = float("-inf")  # plain float: no jax arrays at import time


def latent_decode_attention(
    q: jax.Array,
    cache: jax.Array,
    layer: jax.Array,
    pos: jax.Array,
    *,
    sm_scale: float,
    block_w: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """``softmax(q . rows / ...) rows`` a slot, over the slot's rows
    ``[0, pos]`` of ``cache[layer]``.

    ``q``: (B, H, C) absorbed queries, a row a head, as wide as a cache
    row; ``cache``: (L, B, W, C); ``layer``: int32 scalar and ``pos``: (B,)
    int32, both traced (position ``t`` is attended iff ``t <= pos[b]``: the
    new token's own row is written before the call; ``pos[b] >= W`` is a
    slot with nothing in it: no row of it is read and its result is
    zeros). Returns (B, H, C) float32: the weighted sum of whole rows (the
    caller keeps the first ``rank`` numbers). ``W`` must be a multiple of
    the block (``min(block_w, W)``), C a multiple of 128.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, c = q.shape
    n_layers, bc, w, cc = cache.shape
    assert (bc, cc) == (b, c), (q.shape, cache.shape)
    block_w = min(block_w, w)
    assert w % block_w == 0 and c % 128 == 0, (w, block_w, c)
    n_blocks = w // block_w
    # the CPU backend has no bf16 x bf16 -> f32 product of this form: the
    # interpreter computes in float32 what the chip computes from bfloat16
    compute = jnp.float32 if interpret else cache.dtype
    pos = pos.astype(jnp.int32)
    src, hi = block_bounds(pos, w, block_w)

    def kernel(layer_ref, pos_ref, src_ref, hi_ref,
               q_ref, rows_ref, o_ref, acc, m, l):
        del layer_ref, src_ref, hi_ref
        bb, j = pl.program_id(0), pl.program_id(1)
        depth = pos_ref[bb]

        @pl.when(j == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)
            m[:] = jnp.full_like(m, NEG_INF)
            l[:] = jnp.zeros_like(l)

        @pl.when(jnp.logical_and(depth < w, j * block_w <= depth))
        def _block():
            rows = rows_ref[0, 0].astype(compute)  # (block_w, C)
            scores = jax.lax.dot_general(
                q_ref[0].astype(compute), rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # (H, block_w)
            t = j * block_w + jax.lax.broadcasted_iota(
                jnp.int32, (h, block_w), 1
            )
            scores = jnp.where(t <= depth, scores, NEG_INF)
            m_prev = m[:, :1]
            m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
            pexp = jnp.exp(scores - m_new)  # block 0 always holds t = 0
            corr = jnp.exp(m_prev - m_new)
            l[:, :1] = l[:, :1] * corr + pexp.sum(axis=-1, keepdims=True)
            acc[:] = acc[:] * corr + jax.lax.dot(
                pexp.astype(compute), rows,
                preferred_element_type=jnp.float32,
            )
            m[:, :1] = m_new

        @pl.when(j == n_blocks - 1)
        def _flush():
            lv = l[:, :1]
            o_ref[0] = acc[:] / jnp.where(lv == 0.0, 1.0, lv)  # dead: zeros

    def rows_map(bb, j, layer_ref, pos_ref, src_ref, hi_ref):
        # a block past the slot's depth is the last one needed again, and
        # a dead slot asks for that one alone: the pipeline fetches nothing
        # for an index it already holds
        last = hi_ref[bb]
        blk = jnp.where(pos_ref[bb] < w, jnp.minimum(j, last), last)
        return (layer_ref[0], src_ref[bb], blk, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, n_blocks),
        in_specs=[
            pl.BlockSpec((1, h, c), lambda bb, j, *_: (bb, 0, 0)),
            pl.BlockSpec((1, 1, block_w, c), rows_map),
        ],
        out_specs=pl.BlockSpec((1, h, c), lambda bb, j, *_: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, c), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c), jnp.float32),
        name="latent_decode_attention",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), pos, src, hi, q, cache,
    )


def latent_decode_attention_reference(
    q: jax.Array, rows: jax.Array, valid: jax.Array, *, sm_scale: float
) -> jax.Array:
    """The same sums as plain einsums, for any number of query positions:
    ``q`` (B, S, H, C) against one layer's ``rows`` (B, W, C) under
    ``valid`` (1 | B, S, W). Returns (B, S, H, C) float32."""
    scores = jnp.einsum(
        "bshc,bwc->bhsw", q, rows, preferred_element_type=jnp.float32
    ) * sm_scale
    scores = jnp.where(valid[:, None], scores, jnp.float32(-1e30))
    weights = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
    return jnp.einsum(
        "bhsw,bwc->bhsc", weights, rows, preferred_element_type=jnp.float32
    ).transpose(0, 2, 1, 3)

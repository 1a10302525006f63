"""Decode attention over the carried K and V stacks, read where they lie.

``Attention``'s decode branch (``models/transformer.py``) keeps K and V as
``(B, W, KV, D)`` a layer, stacked ``(L, B, W, KV, D)`` when the layer scan
carries the cache. Written as plain einsums, a step copies the layer's
slice of each out of the stack (a ``dynamic-slice`` the size of the slice)
and then reads the copy twice, over the whole window whatever the slots'
depths: at 32 slots x 2,048 positions some 0.8 GB of traffic a layer where
the live slots' rows are a twentieth of that.

This kernel's work is the live slots' live blocks and nothing else. The
layer index and the slots' depths are **scalar-prefetch** operands; K and
V stay in HBM where the stacks lie (no slice is copied), and the grid walks
the slots in order, a step a slot, each over its blocks ``0 .. pos // rows``
alone by its own DMAs into two buffers of fast memory (:func:`block_walk`
is the schedule). While a block is computed the next one is already in
flight: the slot's next block, or after its last the first block of the
next live slot, so a slot boundary exposes no fetch. The softmax runs as
the streaming ``(m, l, acc)`` recurrence in float32. **A slot that holds no
live sequence costs no rows**: the depth ``W`` (one past the last position,
where a slot's writes already drop) means "nothing here", the slot's step
walks no block and moves no byte, and its result is zeros
(``ServeEngine``'s chain pins the depth of a slot without budget there:
``park_cache_index``). A grid step for every block of every slot, whatever
the depths, cost 22 of a chat call's 26 us and 0.25 ms of a shared-cache
call in the Phi cell, on a v5e (``PERF.md`` section 6).

A block is taken as stored, ``(rows, KV, D)``: its ``(KV, D)`` slabs are the
tiles the chip keeps (``(8, 128)`` in bfloat16), so reshaping or transposing
the stack outside the kernel would copy all of it. Inside, the block is the
matrix ``(rows * KV, D)`` and **every query head multiplies every KV head's
rows**; the scores of the wrong heads are masked like the rows past the
depth. The rows pass the matrix unit once either way (a product of ``H / KV``
query rows a head loads the same tiles), the softmax works on ``KV`` times
the numbers, and that measured twice as fast as reading each head's rows out
of the block by a strided load (``PERF.md`` section 6, PR 31).

One query position a slot (the serving chain's step, ``generate()``'s
step). The products take their operands at the cache's dtype and
accumulate in float32, as the plain einsums do on the chip; the plain path
(:func:`..models.transformer.grouped_masked_attention`) is this kernel's
numerics reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")  # plain float: no jax arrays at import time

_BLOCK_BYTES = 1 << 20  # K and V, two buffers each: 4 MiB of VMEM
_SCOPED_VMEM = 16 << 20  # what the compiler grants a kernel unasked (v5e)


def decode_block(w: int, kv: int, d: int, dtype) -> int | None:
    """Rows of a block for a window of ``w`` positions of ``(kv, d)`` at
    ``dtype``: the largest of 512, 256, 128 that divides ``w`` and keeps a
    block at most 1 MiB (512 for 8 bfloat16 heads of 128; blocks of 256
    measured the same there, of 1,024 a third slower). None where the
    kernel takes no such cache: ``d`` not a whole number of 128-lane tiles,
    or no block that fits. The kernel's every-head-times-every-KV-head
    form does ``kv`` times the score and softmax work: it was measured at
    8 KV heads (bfloat16) and 16 (float32), where the block's DMA holds
    the time; a cache of more KV heads has no measurement behind it."""
    if d % 128:
        return None
    row_bytes = kv * d * jnp.dtype(dtype).itemsize
    for rows in (512, 256, 128):
        if w % rows == 0 and rows * row_bytes <= _BLOCK_BYTES:
            return rows
    return None


# the index maps of latent_decode_attention's grid of (slot, block)
def block_bounds(pos: jax.Array, w: int, block_w: int):
    """Per slot, the slot whose blocks it fetches and the last block index
    it may ask for: a live slot its own blocks up to ``pos // block_w``; a
    dead one (``pos >= w``) that one block only, of the live slot before it
    (the first block of the live slot after it where none is before), which
    the pipeline holds already, so nothing new is fetched."""
    b = pos.shape[0]
    live = pos < w
    idx = jnp.arange(b, dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(live, idx, -1))
    nxt = jax.lax.cummin(jnp.where(live, idx, b), reverse=True)
    last = jnp.where(live, pos // block_w, 0)
    src = jnp.where(prev >= 0, prev, jnp.where(nxt < b, nxt, 0))
    hi = jnp.where(prev >= 0, last[jnp.maximum(prev, 0)], 0)
    return src, hi


def block_walk(pos: jax.Array, w: int, block_w: int):
    """The walk over live blocks, per slot: how many blocks it reads
    (``pos // block_w + 1`` where it is live, none where it is parked,
    ``pos >= w``), how many the slots before it read (so which of the two
    buffers its first block lands in), and the next live slot after it
    (``b`` where none is), whose first block is fetched while the slot's
    last is computed."""
    b = pos.shape[0]
    live = pos < w
    count = jnp.where(live, pos // block_w + 1, 0)
    idx = jnp.arange(b, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(live, idx, b), reverse=True)
    after = jnp.concatenate([nxt[1:], jnp.full((1,), b, jnp.int32)])
    return count, jnp.cumsum(count) - count, after


def decode_attention(
    q: jax.Array,
    k_stack: jax.Array,
    v_stack: jax.Array,
    layer: jax.Array,
    pos: jax.Array,
    *,
    block_w: int | None = None,
    heads_major: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """``softmax(q k^T / sqrt(D)) v`` a slot, over the slot's rows
    ``[0, pos]`` of ``k_stack[layer]`` and ``v_stack[layer]``.

    ``q``: (B, H, D), one query position a slot; ``k_stack``, ``v_stack``:
    (L, B, W, KV, D) with H a multiple of KV (head ``h`` reads KV head
    ``h // (H / KV)``); ``layer``: int32 scalar and ``pos``: (B,) int32,
    both traced. Position ``t`` is attended iff ``t <= pos[b]`` (the new
    token's own row is written before the call); ``pos[b] >= W`` is a slot
    with nothing in it: no row of it is read and its result is zeros.
    Returns (B, H, D) at ``q``'s dtype. ``block_w`` (:func:`decode_block`'s
    by default) must divide ``W``.

    ``heads_major``: the stacks are (L, B, KV, W, D), a KV head's rows
    together. That is how a cache whose KV heads are no whole sublane tile
    has to lie (10 heads: the chip's tiled memory pads a (10, D) slab to
    16 rows, and the compiler then moves the position axis inward itself,
    copying the whole stack into and out of every program: seen in the
    program compiled for a v5e, PR 34). A block is ``(KV, rows, D)`` and
    the matrix row ``c * rows + t``; everything else is the same kernel.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, d = q.shape
    if heads_major:
        n_layers, bc, kv, w, dc = k_stack.shape
    else:
        n_layers, bc, w, kv, dc = k_stack.shape
    assert v_stack.shape == k_stack.shape and (bc, dc) == (b, d), (
        q.shape, k_stack.shape, v_stack.shape
    )
    assert h % kv == 0, (h, kv)
    grp = h // kv
    if block_w is None:
        block_w = decode_block(w, kv, d, k_stack.dtype)
    assert block_w and w % block_w == 0 and d % 128 == 0, (w, block_w, d)
    # a block as a matrix: row t * kv + c is KV head c at t (c * block_w + t
    # where the heads come first)
    n = block_w * kv
    block = (kv, block_w, d) if heads_major else (block_w, kv, d)
    # the CPU backend has no bf16 x bf16 -> f32 product of this form: the
    # interpreter computes in float32 what the chip computes from bfloat16
    compute = jnp.float32 if interpret else k_stack.dtype
    sm_scale = d ** -0.5
    pos = pos.astype(jnp.int32)
    count, first, after = block_walk(pos, w, block_w)

    def kernel(layer_ref, pos_ref, count_ref, first_ref, after_ref,
               q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, acc, m, l):
        bb = pl.program_id(0)
        depth = pos_ref[bb]

        def fetch(slot, blk, buf):
            rows = pl.ds(pl.multiple_of(blk * block_w, block_w), block_w)
            at = (
                (layer_ref[0], slot, slice(None), rows) if heads_major
                else (layer_ref[0], slot, rows)
            )
            return [
                pltpu.make_async_copy(hbm.at[at], dst.at[buf], sem.at[i, buf])
                for i, (hbm, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))
            ]

        # the first live slot's first block; every later block is started
        # while the one before it is computed, across slots too
        first_live = jnp.where(count_ref[0] > 0, 0, after_ref[0])

        @pl.when(jnp.logical_and(bb == 0, first_live < b))
        def _start():
            for cp in fetch(first_live, 0, 0):
                cp.start()

        def step(j, carry):
            buf = jax.lax.rem(first_ref[bb] + j, 2)
            more = j + 1 < count_ref[bb]
            slot = jnp.where(more, bb, after_ref[bb])

            @pl.when(slot < b)
            def _next():
                for cp in fetch(slot, jnp.where(more, j + 1, 0), 1 - buf):
                    cp.start()

            k_cp, v_cp = fetch(bb, j, buf)
            k_cp.wait()
            k2 = k_buf[buf].reshape(n, d).astype(compute)
            scores = jax.lax.dot_general(
                q_ref[bb].astype(compute), k2, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # (H, n)
            col = jax.lax.broadcasted_iota(jnp.int32, (h, n), 1)
            head = jax.lax.broadcasted_iota(jnp.int32, (h, n), 0)
            c, t = (
                (col // block_w, col % block_w) if heads_major
                else (col % kv, col // kv)
            )
            own = jnp.logical_and(c == head // grp, j * block_w + t <= depth)
            scores = jnp.where(own, scores, NEG_INF)
            m_prev = m[:, :1]
            # every block walked holds t = j * block_w <= depth for every head
            m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
            pexp = jnp.exp(scores - m_new)
            corr = jnp.exp(m_prev - m_new)
            l[:, :1] = l[:, :1] * corr + pexp.sum(axis=-1, keepdims=True)
            v_cp.wait()
            v2 = v_buf[buf].reshape(n, d).astype(compute)
            acc[:] = acc[:] * corr + jax.lax.dot(
                pexp.astype(compute), v2, preferred_element_type=jnp.float32
            )
            m[:, :1] = m_new
            return carry

        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG_INF)
        l[:] = jnp.zeros_like(l)
        jax.lax.fori_loop(0, count_ref[bb], step, 0)  # a parked slot: none
        lv = l[:, :1]
        o_ref[bb] = (
            acc[:] / jnp.where(lv == 0.0, 1.0, lv)  # a dead slot: zeros
        ).astype(o_ref.dtype)

    # q and the result lie in VMEM whole: a parked slot's grid step moves
    # no bytes. Past the compiler's default (a few hundred slots of many
    # heads) the call asks for the fast memory that takes.
    vmem = (2 * q.size * q.dtype.itemsize + 4 * n * d * k_stack.dtype.itemsize
            + (h * d + 2 * h * 128) * 4 + (1 << 20))
    q_spec = pl.BlockSpec((b, h, d), lambda bb, *_: (0, 0, 0))
    hbm_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=[q_spec, hbm_spec, hbm_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, *block), k_stack.dtype),
            pltpu.VMEM((2, *block), v_stack.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        # a slot's last block starts the next live slot's first: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem if vmem > _SCOPED_VMEM else None,
        ),
        name="decode_attention",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), pos, count, first, after,
        q, k_stack, v_stack,
    )

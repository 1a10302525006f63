"""Fused paged decode attention: walk the page table, never gather the window.

The gather path (``models/transformer.py`` paged decode branch) reads the
shared ``(pool_pages, page_size, kv_heads, head_dim)`` K/V pools by
materializing each row's whole logical window — ``jnp.take(pool, table,
axis=0)`` into a dense ``(B, W, kv, d)`` temporary — and then runs dense
attention over it. At the HBM roofline that temporary is pure wall time:
full-window KV traffic plus a full-window buffer, every decode step,
regardless of how deep each slot actually is.

This module is the vLLM PagedAttention design (SOSP '23 — the same paper
``serve/pages.py`` cites for the pool) fused the FlashAttention way
(:mod:`.flash_attention` is the house online-softmax template): a Pallas
kernel whose grid walks ``(batch row, logical page)`` with the page table
and per-row ``cache_index`` as **scalar-prefetch** operands, so the K/V
``BlockSpec`` index_maps translate logical page -> physical pool page per
grid step and the kernel only ever holds one page (every kv head's
``(page_size, d)`` tile) at a time. Softmax runs as the streaming
(m, l, acc) recurrence across pages; no dense window exists at any point
— the compiled HLO for a kernel-path decode contains no ``(B, W, ...)``
gathered temporary (tests/test_serve.py pins the shape sweep,
fused_loss-style).

Numerics contract: :func:`paged_attention` matches
:func:`paged_attention_reference` — a pure-jnp restatement of the gather
path's exact math (same f32 score/context accumulation, same validity
rule, ``mode="fill"`` zeros for sentinel pages) — to float tolerance, and
greedy decode through the kernel is token-exact to the gather path
(tests/test_paged_attention.py, tests/test_serve.py). Quantized pools
dequantize **inside** the kernel per page tile (int8 x f32 scales, or
packed int4 nibbles x bf16 scales — :func:`..ops.quant.unpack_int4` is
the reference for the nibble math), so quantized decode traffic stays at
the packed footprint.

Sentinel semantics: the reference gather fills sentinel-backed positions
with 0.0 **rows** and lets the validity mask exclude them; the kernel
skips sentinel pages wholesale (``pl.when``). The two agree everywhere
the engine invariant holds — sentinel pages only back positions beyond a
row's valid length (a parked row, all-sentinel, yields l == 0 and a
discarded zero output). ``quant`` / geometry are ENGINE-STATIC Python
values (the kernel-vs-gather choice itself is ``cfg.paged_kernel``, a
config bool — never a traced value; graftcheck ``traced-control-flow``
has the fixture pair).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_training_tutorials_tpu.ops.quant import (
    dequantize_kv_int4,
    unpack_int4,
)

NEG_INF = float("-inf")  # plain float: no jax arrays at import time

_QUANT_MODES = (None, "int8", "int4")


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    pos: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    quant: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged decode attention straight off the page pools.

    ``q``: (B, S, H, D) queries (already rope'd; S >= 1 covers the
    chunked-continuation decode). ``k_pool``/``v_pool``: (N_pages,
    page_size, KV, D) shared pools — (.., D // 2) packed uint8 when
    ``quant == "int4"``. ``table``: (B, P) int32 page table (sentinel =
    N_pages, out of range). ``pos``: (B,) int32 per-row cache depth
    (query row s sits at global position ``pos + s``; positions
    ``t <= pos + s`` are attended — the gather path's validity rule).
    ``k_scale``/``v_scale``: (N_pages, page_size, KV) per-token-per-head
    scales, required iff ``quant`` is "int8" (f32) or "int4" (bf16).

    ``quant`` and every shape are engine-static; ``table``/``pos`` are
    traced data and reach the kernel as scalar-prefetch operands (their
    values steer BlockSpec index_maps, never Python control flow).
    ``interpret=None`` auto-selects interpreter mode off-TPU, like every
    kernel in ops/. Returns (B, S, H, D) in ``q.dtype``.

    Real-TPU tiling note: the Mosaic lowering takes a block only when
    each of its last two dims is the whole array dim or a multiple of
    (8, 128), so no block may slice one head out of a ``(.., H, D)`` or
    ``(.., KV, D)`` array. The kernel therefore sees q and the output as
    ``(B, KV, S * grp, D)`` (one whole ``(S * grp, D)`` slab per kv head,
    any grp and S) and each pool as ``(N_pages, page_size, KV * D)`` — a
    free reshape whose page row is one lane-dense block; a grid step
    fetches one whole page and serves every kv head from static lane
    slices of it. That leaves ``page_size`` (sublane) a multiple of 8 and
    the stored head width (``D``, or ``D // 2`` packed) a multiple of the
    dtype's lane tile as the geometry the chip's compiler is known to
    take; ``tests/test_chip_compile.py`` holds the serving presets'
    shapes (D 128, page_size 64, KV 16 and 4, bf16/int8/int4).
    """
    if quant not in _QUANT_MODES:
        raise ValueError(f"quant must be one of {_QUANT_MODES}, got {quant!r}")
    if (quant is not None) != (k_scale is not None and v_scale is not None):
        raise ValueError(
            "k_scale/v_scale are required exactly when quant is set"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, d = q.shape
    n_pages, page_size, kv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    if h % kv:
        raise ValueError(f"n_heads {h} must be a multiple of kv_heads {kv}")
    grp = h // kv
    p_cap = table.shape[1]
    d_store = d // 2 if quant == "int4" else d
    if k_pool.shape[3] != d_store:
        raise ValueError(
            f"pool head_dim {k_pool.shape[3]} != expected {d_store} "
            f"(quant={quant!r}, q head_dim {d})"
        )
    sg = s * grp
    # compute dtypes mirror the gather path: quantized pools dequantize to
    # the query compute dtype; full-precision scores promote q x storage
    kv_dtype = q.dtype if quant else k_pool.dtype
    score_dtype = jnp.promote_types(q.dtype, kv_dtype)
    sm_scale = 1.0 / (d**0.5)

    def kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, *rest):
        if quant:
            ks_ref, vs_ref, o_ref, acc, m, l = rest
        else:
            ks_ref = vs_ref = None
            o_ref, acc, m, l = rest
        bb = pl.program_id(0)
        p = pl.program_id(1)

        @pl.when(p == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)
            m[:] = jnp.full_like(m, NEG_INF)
            l[:] = jnp.zeros_like(l)

        pid = tbl_ref[bb, p]
        depth = pos_ref[bb]
        # whole-page skip: sentinel/unbacked pages and pages entirely past
        # the deepest query position contribute exact zeros either way
        # (exp(-inf - shift) == 0.0), so skipping them is free AND exact
        live = jnp.logical_and(pid < n_pages, p * page_size <= depth + (s - 1))

        def head_tile(ref, s_ref, hh):
            """Head ``hh``'s (page_size, D) tile of the page, dequantized:
            a static lane slice of the page's (page_size, KV * D) row."""
            tile = ref[0, :, hh * d_store:(hh + 1) * d_store]
            if not quant:
                return tile
            scale = s_ref[0, :, hh:hh + 1].astype(jnp.float32)
            if quant == "int4":
                # widen first: the v5e vector unit has no 8-bit shifts
                # (Mosaic refuses arith.shrui on i8)
                tile = unpack_int4(tile.astype(jnp.int32))
            return (tile.astype(jnp.float32) * scale).astype(kv_dtype)

        @pl.when(live)
        def _page():
            # validity: global position t attends iff t <= pos + s_row
            # (row r of the (sg, page_size) tile is query s_row = r // grp)
            srow = jax.lax.broadcasted_iota(jnp.int32, (sg, page_size), 0)
            t = p * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (sg, page_size), 1
            )
            valid = t <= depth + srow // grp
            for hh in range(kv):  # static: one page fetch serves every head
                kb = head_tile(k_ref, ks_ref, hh)
                vb = head_tile(v_ref, vs_ref, hh)
                scores = jax.lax.dot_general(
                    q_ref[0, hh].astype(score_dtype),
                    kb.astype(score_dtype),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * sm_scale
                scores = jnp.where(valid, scores, NEG_INF)
                m_prev = m[hh, :, :1]
                m_new = jnp.maximum(
                    m_prev, scores.max(axis=-1, keepdims=True)
                )
                shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
                pexp = jnp.exp(scores - shift)
                corr = jnp.exp(m_prev - shift)
                l[hh, :, :1] = (
                    l[hh, :, :1] * corr + pexp.sum(axis=-1, keepdims=True)
                )
                acc[hh] = acc[hh] * corr + jax.lax.dot(
                    pexp.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32,
                )
                m[hh, :, :1] = m_new

        @pl.when(p == pl.num_programs(1) - 1)
        def _flush():
            lv = l[:, :, :1]
            safe = jnp.where(lv == 0.0, 1.0, lv)  # all-parked row -> 0 out
            o_ref[0] = (acc[:] / safe).astype(o_ref.dtype)

    # Kernel-side layouts (see the tiling note): queries grouped per kv
    # head, pools with (KV, D) flattened into one lane-dense page row.
    qk = (
        q.reshape(b, s, kv, grp, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, kv, sg, d)
    )
    row = kv * d_store

    # index_maps read the prefetched table: logical page p of row b lives
    # at pool page table[b, p] — sentinels clamp in-range for the FETCH
    # (the block must exist) and the kernel's `live` predicate masks them
    def _pool_map(bb, p, tbl, _pos):
        return (jnp.minimum(tbl[bb, p], n_pages - 1), 0, 0)

    def _q_map(bb, p, tbl, _pos):
        return (bb, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, kv, sg, d), _q_map),
        pl.BlockSpec((1, page_size, row), _pool_map),
        pl.BlockSpec((1, page_size, row), _pool_map),
    ]
    operands = [
        qk,
        k_pool.reshape(n_pages, page_size, row),
        v_pool.reshape(n_pages, page_size, row),
    ]
    if quant:
        in_specs += [
            pl.BlockSpec((1, page_size, kv), _pool_map),
            pl.BlockSpec((1, page_size, kv), _pool_map),
        ]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, p_cap),  # pages innermost: the online-softmax carry
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv, sg, d), _q_map),
        scratch_shapes=[
            pltpu.VMEM((kv, sg, d), jnp.float32),
            pltpu.VMEM((kv, sg, 128), jnp.float32),
            pltpu.VMEM((kv, sg, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, sg, d), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(table, pos, *operands)
    return (
        out.reshape(b, kv, s, grp, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, s, h, d)
    )


def paged_attention_reference(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    pos: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    quant: str | None = None,
) -> jax.Array:
    """Pure-jnp statement of the gather path's math — the oracle the
    kernel pins against, self-contained so tests need no model: gather
    whole pages dense (``jnp.take`` ``mode="fill"`` zeros for sentinels),
    dequantize, then the grouped masked attention of
    ``models.transformer`` (f32 score/softmax/context accumulation,
    validity ``t <= pos + s``)."""
    if quant not in _QUANT_MODES:
        raise ValueError(f"quant must be one of {_QUANT_MODES}, got {quant!r}")
    b, s, h, d = q.shape
    page_size, kv = k_pool.shape[1], k_pool.shape[2]
    w = table.shape[1] * page_size

    def gather(pool):
        out = jnp.take(pool, table, axis=0, mode="fill", fill_value=0)
        return out.reshape((b, w) + pool.shape[2:])

    if quant == "int8":
        k = (
            gather(k_pool).astype(jnp.float32)
            * gather(k_scale)[..., None]
        ).astype(q.dtype)
        v = (
            gather(v_pool).astype(jnp.float32)
            * gather(v_scale)[..., None]
        ).astype(q.dtype)
    elif quant == "int4":
        k = dequantize_kv_int4(gather(k_pool), gather(k_scale), q.dtype)
        v = dequantize_kv_int4(gather(v_pool), gather(v_scale), q.dtype)
    else:
        k, v = gather(k_pool), gather(v_pool)

    qpos = pos[:, None] + jnp.arange(s)
    valid = jnp.arange(w) <= qpos[..., :, None]  # (B, S, W)
    grp = h // kv
    q5 = q.reshape(b, s, kv, grp, d)
    scores = jnp.einsum(
        "bqcgd,blcd->bcgql", q5, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(
        valid[:, None, :, :][:, :, None], scores, jnp.float32(-1e30)
    )
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum(
        "bcgql,blcd->bqcgd", weights, v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype).reshape(b, s, h, d)

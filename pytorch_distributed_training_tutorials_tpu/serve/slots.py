"""Slot-indexed KV-cache state: the device side of continuous batching.

The engine serves ``n_slots`` concurrent requests out of ONE fixed-shape
cache tree whose batch axis is the slot axis — the TPU-native analogue of
vLLM's block-managed cache (SOSP '23): XLA wants one compiled program over
static shapes, so instead of paging, every request is given a whole
fixed-size slot and finished slots are REFILLED in place
(``dynamic_update_slice`` of a freshly prefilled K/V block plus a per-slot
position reset) without recompiling anything.

Three pieces live here:

- :func:`init_slot_state` — build the zeroed slot-state pytree from the
  model's own cache schema (``jax.eval_shape``: no FLOPs, no buffers until
  the zeros are actually created), with ``cache_index`` widened from the
  scalar ``generate()`` layout to a ``(n_slots,)`` vector so each slot
  decodes at its own depth (``models/transformer.py`` branches on the
  trace-time rank);
- :func:`bucket_len` — prompt-length buckets (powers of two, floor 8) so
  prefill compiles once per bucket instead of once per prompt length;
- :func:`write_slot` — the refill: one traced tree-surgery pass that
  splices a batch-1 prefill cache into slot ``s`` of the big cache and
  resets that slot's position counter, inside whatever jit it is called
  from (slot index and prompt length are traced scalars — no recompile
  per slot or per length);
- :func:`extract_segment` / :func:`seed_cache` / :func:`tree_nbytes` —
  the device half of the prefix cache (:mod:`.prefix`): cut a retained
  prefix segment out of a batch-1 prefilled cache (static bucket length
  on the sequence axis, so segment shapes reuse the pow2 bucket set and
  splices never recompile per prompt), seed a fresh batch-1 cache from
  one, and size a segment host-side from leaf metadata (no device
  fetch — the index's byte accounting must not break the engine's
  one-fetch-per-chain budget).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def bucket_len(p_len: int, window: int, floor: int = 8) -> int:
    """Static prefill length for a ``p_len``-token prompt: the next power
    of two >= ``p_len`` (>= ``floor``, TPU-sublane-friendly), capped at the
    serving window. Prompts are right-padded to the bucket; causal
    attention makes positions ``[0, p_len)`` independent of the padding
    tail, and the next-token logits are gathered at ``p_len - 1``
    (``TransformerLM.__call__(last_pos=...)``), so bucketing changes
    compile-cache hit rate, never results. A recurrent state is not
    independent of what follows it: a model that keeps one stops it at
    ``p_len`` itself (``models/sambay.py``: past the prompt ``delta = 0``,
    the identity on the state, and its rings take the rows before
    ``p_len``), which the same ``last_pos`` tells it."""
    if p_len < 1:
        raise ValueError("p_len must be >= 1")
    b = floor
    while b < p_len:
        b *= 2
    return min(b, window)


def init_slot_state(model, params, n_slots: int, history: int = 0,
                    adapters: bool = False, paged: int = 0,
                    strategy=None):
    """Zero-initialized slot-state pytree for ``n_slots`` concurrent
    requests of ``model`` (a :class:`..models.transformer.TransformerLM`
    or anything sharing its cache contract).

    The cache schema comes from the model itself via ``jax.eval_shape`` of
    a decode apply — zero FLOPs, zero device buffers — so GQA, int8 KV
    scales, and ``scan_layers``-stacked leaves are all picked up without
    this module knowing their shapes. ``cache_index`` leaves (scalar per
    layer in the ``generate()`` layout; ``(L,)`` stacked under
    ``nn.scan``) grow a trailing ``(n_slots,)`` axis — the per-slot
    position counters.

    Returns ``{"cache", "last_tok", "keys", "remaining"}``:
    ``last_tok`` ``(S,)`` int32 — each slot's most recent token (the next
    decode input); ``keys`` ``(S, 2)`` uint32 — per-slot PRNG streams
    (:func:`..models.sampling.sample_logits_per_slot`); ``remaining``
    ``(S,)`` int32 — tokens still to generate, 0 = slot free/parked (the
    active mask is ``remaining > 0``).

    ``history > 0`` (the engine passes its window when speculate-k is on)
    adds the per-slot recent-token buffer the on-device n-gram draft
    feeds on (:func:`..models.sampling.ngram_draft`): ``hist`` ``(S,
    history)`` int32 — each slot's known tokens, prompt + emitted, junk
    beyond ``hist_len`` — and ``hist_len`` ``(S,)`` int32. Both are
    reseeded at refill and carried through the decode chain, so drafting
    never costs a host round-trip. Speculation off keeps the state tree
    (and therefore every compiled program) byte-identical to the
    pre-speculation engine.

    ``adapters=True`` (the engine passes it when an adapter bank is
    attached) adds ``adapter_ids`` ``(S,)`` int32 — each slot's LoRA bank
    row, set at prefill/splice and carried through the chain as the
    per-row gather index of :func:`..adapters.bank.apply_lora`. Same
    off-state contract as speculation: adapters off keeps the state tree
    byte-identical.

    ``paged`` (the pool's ``pool_pages``, 0 = off) builds the state for a
    PAGED model (``TransformerConfig(kv_pages=..., kv_page_size=...)``):
    the model's own schema already declares the shared pools, the
    ``(n_slots, P)`` page tables, and per-row ``(n_slots,)`` position
    counters — no widening needed — and every ``page_table`` leaf is
    filled with the sentinel id ``paged`` (== ``kv_pages``, out of
    range), so an unbacked slot's decode writes DROP instead of
    corrupting pool pages (see ``models/transformer.py
    _store_paged_kv``).

    ``strategy`` (a :class:`..parallel.tensor_parallel.TensorParallel`
    with ``tp_size > 1``; ISSUE 15) places the finished tree per the
    strategy's slot rules — K/V (and pool) leaves head-sharded to match
    the attention split, bookkeeping replicated. Committed sharded
    inputs are what make the engine's jits compile GSPMD-sharded decode
    programs; ``strategy=None`` (or tp 1) leaves placement untouched,
    byte-identical to the pre-sharding builder.
    """
    if n_slots < 1:
        raise ValueError("n_slots must be >= 1")

    def cache_shape(p, t):
        return model.apply(
            {"params": p}, t, decode=True, mutable=["cache"]
        )[1]["cache"]

    shapes = jax.eval_shape(
        cache_shape, params, jnp.zeros((n_slots, 1), jnp.int32)
    )

    def build(path, leaf):
        if _leaf_name(path) == "cache_index":
            if paged:
                # the paged schema already declares (S,) / (L, S)
                return jnp.zeros(leaf.shape, jnp.int32)
            # () -> (S,), or (L,) -> (L, S) under scan_layers
            return jnp.zeros(leaf.shape + (n_slots,), jnp.int32)
        if _leaf_name(path) == "page_table":
            return jnp.full(leaf.shape, paged, jnp.int32)
        return jnp.zeros(leaf.shape, leaf.dtype)

    state = {
        "cache": jax.tree_util.tree_map_with_path(build, shapes),
        "last_tok": jnp.zeros((n_slots,), jnp.int32),
        "keys": jnp.zeros((n_slots, 2), jnp.uint32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
    }
    if history > 0:
        state["hist"] = jnp.zeros((n_slots, history), jnp.int32)
        state["hist_len"] = jnp.zeros((n_slots,), jnp.int32)
    if adapters:
        state["adapter_ids"] = jnp.zeros((n_slots,), jnp.int32)
    if strategy is not None and getattr(strategy, "tp_size", 1) > 1:
        state = strategy.shard_slot_state(state)
    return state


@jax.named_scope("kv_cache")
def write_slot(cache, prefill_cache, slot, p_len, scan_layers: bool):
    """Splice a batch-1 prefilled cache into slot ``slot`` of the big
    slot-indexed ``cache`` and reset that slot's position to ``p_len`` —
    the refill that lets a finished slot host a new request without
    recompiling the decode program.

    ``slot`` and ``p_len`` may be traced scalars (they are, inside the
    engine's jitted prefill). K/V (and int8 scale) leaves update by
    ``dynamic_update_slice`` along the slot axis — axis 0, or axis 1 under
    ``scan_layers`` where every leaf carries a leading layer axis; the
    rank alone cannot distinguish the two layouts (a scanned int8 scale
    and an unrolled K/V block are both rank 4), hence the explicit flag.
    ``cache_index`` leaves set position ``slot`` on their trailing slot
    axis. Bucket padding beyond ``p_len`` carries garbage K/V; it is
    masked by the per-slot validity row until the decode writes of this
    very request overwrite it (positions advance from ``p_len``), so it
    is never read.
    """

    def upd(path, big, pre):
        if _leaf_name(path) == "cache_index":
            return big.at[..., slot].set(jnp.asarray(p_len, big.dtype))
        start = (0, slot) if scan_layers else (slot,)
        start = start + (0,) * (big.ndim - len(start))
        return jax.lax.dynamic_update_slice(
            big, pre.astype(big.dtype), start
        )

    return jax.tree_util.tree_map_with_path(upd, cache, prefill_cache)


# pool-leaf name -> the flat (unpaged) cache leaf it is filled from: the
# engine prefills through the UNPAGED model (classic whole-window batch-1
# cache), then write_slot_paged scatters that cache into the shared pools.
# Quantized KV reuses the same four names for BOTH families (int8 storage
# + f32 scales, and ISSUE 17's int4 packed-nibble uint8 storage + bf16
# scales — models/transformer.py _kv_storage): only dtypes and the packed
# head_dim change, so this map, the seq-axis reshape in write_slot_paged,
# and parallel.SLOT_STATE_RULES cover int4 without a new case.
_POOL_TO_FLAT = {
    "paged_key": "cached_key",
    "paged_value": "cached_value",
    "paged_key_scale": "cached_key_scale",
    "paged_value_scale": "cached_value_scale",
}


def _path_strs(path) -> tuple:
    """tree_map_with_path key path as a tuple of plain strings."""
    return tuple(
        str(getattr(k, "key", getattr(k, "idx", k))) for k in path
    )


@jax.named_scope("kv_cache")
def write_slot_paged(cache, prefill_cache, row, slot, p_len,
                     page_size: int, scan_layers: bool):
    """Paged refill: scatter a batch-1 UNPAGED prefilled cache into the
    page-pool ``cache`` at the page ids of ``row``, install ``row`` as
    slot ``slot``'s page table, and reset its position to ``p_len`` —
    the paged twin of :func:`write_slot`.

    ``row`` is the slot's full ``(P,)`` int32 page-table vector: freshly
    allocated ids for the pages the request backs, the sentinel
    (``kv_pages``) beyond. The prefill cache is full-window (prefill
    zero-inits ``(1, max_seq_len, ...)`` and writes ``[0, bucket)``), so
    reshaping its sequence axis to ``(P, page_size)`` yields every
    logical page; the ``mode="drop"`` scatter writes the allocated ones
    WHOLE — which doubles as the pool's sanitizer: any junk a previous
    holder's in-flight chain wrote into a recycled page is fully
    overwritten before this slot's first read (the engine dispatches the
    refill AFTER any chain still holding the old table — device program
    order). Sentinel rows drop. ``slot``/``p_len``/``row`` may be traced
    (they are, inside the engine's jitted paged prefill) — no recompile
    per slot, per length, or per page assignment."""
    flat = {
        _path_strs(p): leaf
        for p, leaf in jax.tree_util.tree_leaves_with_path(prefill_cache)
    }

    def upd(path, big):
        name = _leaf_name(path)
        if name == "page_table":
            return big.at[..., slot, :].set(jnp.asarray(row, big.dtype))
        if name == "cache_index":
            return big.at[..., slot].set(jnp.asarray(p_len, big.dtype))
        src = flat[_path_strs(path)[:-1] + (_POOL_TO_FLAT[name],)]
        if scan_layers:
            # (L, 1, W, ...) -> (L, P, page_size, ...)
            pages = src.reshape(
                (src.shape[0], -1, page_size) + src.shape[3:]
            )
            return big.at[:, row].set(pages.astype(big.dtype), mode="drop")
        # (1, W, ...) -> (P, page_size, ...)
        pages = src.reshape((-1, page_size) + src.shape[2:])
        return big.at[row].set(pages.astype(big.dtype), mode="drop")

    return jax.tree_util.tree_map_with_path(upd, cache)


@jax.named_scope("kv_cache")
def extract_segment(cache, seg_len: int, scan_layers: bool):
    """Cut the first ``seg_len`` sequence positions out of a batch-1
    prefilled ``cache`` tree — the retained prefix segment the radix
    index (:mod:`.prefix`) keeps alive, and since ISSUE 18 also the
    transfer payload of a prefill/decode handoff: a ``role="prefill"``
    engine cuts the prompt's whole pow2 bucket here and ships it as
    ``Handoff.segment`` (device resident, never fetched); the decode
    replica's accept replays the :func:`seed_cache` + :func:`write_slot`
    splice surgery, so the transplant is bitwise the monolithic
    post-prefill slot state.

    ``seg_len`` is STATIC (a pow2 ``bucket_len`` of the prefix length):
    segment shapes come from the same bucket set prefill compiles
    against, so a splice over any retained segment hits an existing
    compile instead of minting one per prompt length. The sequence axis
    is 1, or 2 under ``scan_layers`` (leading layer axis) — same layout
    rule as :func:`write_slot`. ``cache_index`` leaves pass through
    untouched; their value is dead weight (a handful of int32s) that
    :func:`seed_cache` overwrites with the matched depth. Positions in
    ``[real prefix, seg_len)`` hold bucket-padding garbage — safe because
    a consumer only reuses ``[0, depth)`` with ``depth <= real prefix``
    and overwrites/masks everything beyond (see :mod:`.prefix`)."""
    ax = 2 if scan_layers else 1

    def cut(path, leaf):
        if _leaf_name(path) == "cache_index":
            return leaf
        sl = [slice(None)] * leaf.ndim
        sl[ax] = slice(0, seg_len)
        return leaf[tuple(sl)]

    return jax.tree_util.tree_map_with_path(cut, cache)


@jax.named_scope("kv_cache")
def seed_cache(proto, segment, depth):
    """Build a batch-1 full-window cache whose ``[0, seg_len)`` positions
    come from a retained ``segment`` and whose position counters read
    ``depth`` — the device-side start state of a prefix-cache hit: the
    suffix prefill then continues from position ``depth`` exactly as if
    positions ``[0, depth)`` had just been prefilled (bit-equal for
    full-precision caches, tests/test_transformer.py pins it).

    ``proto`` is a shape/dtype pytree of the batch-1 decode cache (the
    engine evals it once at construction); ``depth`` may be traced. The
    segment lands at the tree origin (it IS the leading seq chunk, on
    every layout — unrolled, scanned, int8 scales), so one origin
    ``dynamic_update_slice`` per leaf covers all of them."""

    def seed(path, p, seg):
        if _leaf_name(path) == "cache_index":
            return jnp.full(p.shape, depth, jnp.int32)
        z = jnp.zeros(p.shape, p.dtype)
        return jax.lax.dynamic_update_slice(
            z, seg.astype(p.dtype), (0,) * z.ndim
        )

    return jax.tree_util.tree_map_with_path(seed, proto, segment)


def zero_cache(proto):
    """Zeroed batch-1 full-window cache from a shape/dtype ``proto`` —
    the start state of a from-scratch CHUNKED prefill (ISSUE 11):
    ``cache_index`` reads 0, so the first chunk's decode continuation
    writes from position 0 exactly as a whole prefill would, and every
    later chunk continues where the previous one stopped (the same
    bitwise-equal continuation :func:`seed_cache` splices rely on, just
    starting at depth 0)."""

    def z(path, p):
        if _leaf_name(path) == "cache_index":
            return jnp.zeros(p.shape, jnp.int32)
        return jnp.zeros(p.shape, p.dtype)

    return jax.tree_util.tree_map_with_path(z, proto)


def tree_nbytes(tree) -> int:
    """Total bytes of a pytree's array leaves, from shape/dtype metadata
    only — works on concrete arrays AND ``jax.eval_shape`` structs, and
    never touches the device (the prefix index budgets bytes without
    spending a host fetch)."""
    return sum(
        math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def tree_nbytes_sharded(tree) -> int:
    """Per-DEVICE bytes of a pytree's array leaves: each leaf priced at
    its shard shape (``sharding.shard_shape``) instead of its global
    shape, so a head-sharded KV segment on a tp-wide mesh costs
    ``1/tp`` of its global bytes — the honest per-chip HBM claim
    (ISSUE 15). Falls back to global shape for leaves without a
    concrete sharding (eval_shape structs, plain numpy), making it a
    drop-in for :func:`tree_nbytes` on replicated trees. Metadata only —
    never a device fetch."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        shape = (
            sharding.shard_shape(leaf.shape)
            if sharding is not None else leaf.shape
        )
        total += math.prod(shape) * jnp.dtype(leaf.dtype).itemsize
    return total


# what a whole-slot cache leaf is, by its name (models/transformer.py and
# models/sambay.py declare them): K and V over the whole window, a ring of
# the newest rows, recurrent state. Counters and page pools are no slot's.
_SLOT_LEAF_KINDS = {
    "cached_key": "kv", "cached_value": "kv", "cached_key_scale": "kv",
    "cached_value_scale": "kv", "cached_latent": "kv",
    "shared_key": "kv", "shared_value": "kv",
    "window_key": "ring", "window_value": "ring",
    "ssm_state": "state", "conv_state": "state",
}


def slot_bytes(cache, n_slots: int) -> dict[str, int]:
    """``{"slot_kv_bytes", "slot_ring_bytes", "slot_state_bytes"}``: the
    bytes one of ``n_slots`` slots holds of ``cache``, from shapes alone."""
    out = {"slot_kv_bytes": 0, "slot_ring_bytes": 0, "slot_state_bytes": 0}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        kind = _SLOT_LEAF_KINDS.get(_leaf_name(path))
        if kind:
            out[f"slot_{kind}_bytes"] += tree_nbytes(leaf) // n_slots
    return out


def _leaf_name(path) -> str:
    """Last key of a tree_map_with_path key path, as a plain string."""
    k = path[-1]
    return str(getattr(k, "key", getattr(k, "idx", k)))

"""Decoder-only transformer LM, TPU-first.

The reference's only transformer is the vendored Llama-7B it *loads* for the
``device_map="auto"`` placement demo — never run on a prompt
(``03.model_parallel.ipynb`` cell 2; SURVEY.md C13, section 5.7). This module
supplies the model family the framework needs first-class: a Llama-style
decoder (RMSNorm, rotary positions, SwiGLU) written for XLA:

- static shapes, no data-dependent Python control flow; optional
  ``nn.scan`` over layers (``scan_layers=True``) for O(1) compile time at
  depth, and optional ``nn.remat`` (``remat=True``) to trade FLOPs for HBM.
- bf16-friendly: params stay float32, compute casts to ``cfg.dtype`` at the
  matmuls; softmax and RMS statistics in float32.
- the attention inner loop is pluggable (``attention_fn``) so sequence-
  parallel ring attention (:mod:`..parallel.ring_attention`) slots in without
  touching the module.
- placement-free: tensor-parallel sharding lives in :data:`TP_RULES`
  (param-path regex -> PartitionSpec), consumed by
  :class:`..parallel.tensor_parallel.TensorParallel` — the Megatron-style
  column/row split expressed as GSPMD annotations.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_tutorials_tpu.models.moe import (
    MOE_RULES,
    MoEFFN,
    RoutedExperts,
)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    d_ff: int | None = None  # default 4 * d_model
    # grouped-query attention (GQA; 1 = MQA): K/V are projected to this
    # many heads and the KV cache stores only them — n_heads/n_kv_heads
    # query heads share each KV head (repeated at attention time). None =
    # n_heads (standard MHA). Serving win: cache bytes scale with
    # n_kv_heads (Llama-2-70B-style 8x reduction at 64/8 heads).
    n_kv_heads: int | None = None
    max_seq_len: int = 512
    dtype: jnp.dtype = jnp.float32
    rope_theta: float = 10000.0
    # RMSNorm epsilon. 1e-6 is the Llama-1 value; published Llama-2/3
    # checkpoints (parallel/hf_llama.py ingestion) use 1e-5 — exact logit
    # parity with the source model requires matching it.
    norm_eps: float = 1e-6
    scan_layers: bool = False
    remat: bool = False
    # What remat may KEEP instead of recomputing (jax.checkpoint policy):
    # None = full remat (recompute everything in the block — minimum HBM,
    # ~1/3 extra matmul FLOPs in the backward); "dots" =
    # checkpoint_dots_with_no_batch_dims_saveable (save matmul outputs,
    # recompute only the cheap elementwise/norm ops — the standard LLM
    # trade: backward matmul recompute disappears for ~2x the activation
    # footprint of full remat). Measured on the v5e (round 5):
    # "dots" lifts the 350m train step's MFU materially over full remat.
    remat_policy: str | None = None
    # attention_fn(q, k, v) -> out, all (B, S, H, D), causal semantics.
    # None = dense causal softmax attention on-device.
    attention_fn: Callable | None = None
    # Mixture-of-Experts: >0 replaces every block's dense FFN with a routed
    # MoEFFN of that many experts (see models/moe.py; shard with ep_rules()).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # tokens per MoE routing/capacity group (None = one S-token group;
    # see models/moe.py's memory-ceiling note — set for long sequences)
    moe_group_size: int | None = None
    # int8 serving: every matmul weight becomes an Int8Dense(General) over
    # the Pallas MXU kernel (the load_in_8bit twin, SURVEY C13). Params come
    # from quantize_lm_params(f32_params) or load_quantized_lm(path);
    # training is not supported.
    quantized: bool = False
    # KV-cache storage dtype (None = follow the K/V compute dtype, exact).
    # At long windows decode is CACHE-bound, not weight-bound (the 1b
    # preset at a 2080-token window reads ~2.2 GB f32 of cache vs ~1.2 GB
    # int8 of weights per step — round 4); jnp.bfloat16 halves that
    # traffic, and jnp.int8 quarters it (per-token-per-head absmax scales
    # stored alongside — _quantize_kv — at ~1.06 bytes/element all-in).
    # Opt-in because it rounds stored K/V: greedy tokens can diverge from
    # the f32-cache reference at near-ties (both attention matmuls still
    # accumulate f32 — masked_attention sets preferred_element_type on
    # the scores AND the context einsum — so the only loss is the storage
    # rounding itself; int8 rounds harder than bf16). The string "int4"
    # selects packed-nibble storage (two int4 values per uint8 byte along
    # head_dim, per-token-per-head absmax scales stored bfloat16 —
    # ops.quant.quantize_kv_int4): EXACTLY half the int8 cache bytes per
    # token-head (D/2 + 2 vs D + 4), the 2x-pages-per-pool claim. dtype
    # strings ("int8"/"bf16"/...) normalize through _kv_quant_mode /
    # jnp.dtype, so "int8" and jnp.int8 are the same config.
    kv_cache_dtype: "jnp.dtype | str | None" = None
    # Paged KV decode (serve/pages.py, ISSUE 13): > 0 restructures the
    # DECODE cache as one shared (kv_pages, kv_page_size, heads, head_dim)
    # pool per layer plus a per-row int32 page-table vector riding the
    # cache tree as DATA — reads gather whole pages by table entry
    # (jnp.take, mode="fill"), writes scatter through the table
    # (mode="drop"; the sentinel id kv_pages maps unbacked logical pages
    # out of range so their writes vanish). Page ids are traced data,
    # never Python control flow — the adapter-bank discipline. Governs
    # decode=True only; prefill keeps the classic whole-window batch-1
    # cache (serve/engine.py prefills unpaged and scatters the result
    # into the pool via slots.write_slot_paged). 0 = feature off:
    # programs and cache trees byte-identical to a pre-paging build.
    kv_pages: int = 0
    kv_page_size: int = 0
    # Fused paged-attention kernel (ops/paged_attention.py, ISSUE 17):
    # True makes the paged decode branch compute attention straight off
    # the page pools via the Pallas online-softmax kernel — the page
    # table is a scalar-prefetch operand steering BlockSpec index_maps,
    # so no dense (B, max_seq_len, ...) gathered window is ever
    # materialized (the jnp.take gather path remains the numerics
    # reference and the False default). ENGINE-STATIC by construction:
    # a config bool read at trace time, never a traced value (graftcheck
    # traced-control-flow pins the anti-pattern). Decode-only, like
    # kv_pages itself; requires kv_pages > 0 to have any effect.
    paged_kernel: bool = False
    # The mesh a tensor-parallel model is served under (None = one device,
    # or replicated): what the model's Pallas kernels look at, since a bare
    # pallas_call is refused on operands GSPMD has sharded. ServeEngine
    # sets it from its strategy (tp > 1); a caller that runs generate() on
    # sharded params sets it themselves. Under it the decode step keeps
    # the plain head-sharded einsums (ops.decode_attention is not called),
    # and a quantized model (int8 serving) routes every matmul through the
    # shard_map-wrapped kernel (ops.quant.int8_matmul_tp) in the Megatron
    # column/row layout, with q/scale params sharded per INT8_TP_RULES:
    # that requires n_heads, ff_dim, vocab_size and d_model divisible by
    # the model-axis size (and n_kv_heads for a GQA model; a non-divisible
    # dim falls back to replication under the float TP rules —
    # parallel.tensor_parallel.spec_for_path drops the axis shape-aware).
    tp_mesh: "jax.sharding.Mesh | None" = None
    # Multi-tenant LoRA (adapters/): > 0 equips every attention/MLP
    # projection with a stacked (lora_adapters, ..., lora_rank) delta bank
    # gathered per batch row by an adapter-id VECTOR inside the compiled
    # program (adapters.bank.apply_lora) — row 0 is the base model (zero
    # factors, kept zero by construction), so heterogeneous tenants
    # co-batch in one program with no recompile: ids are data, only
    # lora_adapters/lora_rank are static. 0 = feature off: params and
    # compiled programs are byte-identical to a build without LoRA.
    lora_adapters: int = 0
    lora_rank: int = 0
    # Latent attention (MLA): kv_lora_rank > 0 replaces the block's
    # attention with LatentAttention. Queries go through a q_lora_rank
    # bottleneck with a norm; keys and values come from ONE kv_lora_rank
    # latent a token (normed) plus qk_rope_head_dim rotary dims shared by
    # all heads, and the cache holds just those kv_lora_rank +
    # qk_rope_head_dim numbers a token and a layer ("cached_latent").
    # Prefill up-projects K and V a head and attends blockwise; decode
    # reads the latent cache itself, the up-projection absorbed into the
    # query and the output. n_kv_heads and head_dim mean nothing then.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # a norm after each sublayer too, before it joins the residual stream
    sandwich_norm: bool = False
    # Routed experts that drop no token (models/moe.py RoutedExperts), as
    # one chip's share: n_routed_experts > 0 gives the layers from
    # n_dense_layers on a router over n_routed_experts, experts_per_token
    # of them a token, of which this model holds experts_held (None: all)
    # from expert_offset, and n_shared_experts shared experts, each a
    # SwiGLU of width expert_d_ff. The n_dense_layers leading layers keep
    # the dense SwiGLU of width d_ff; a model with both kinds of layer runs
    # its layers unrolled (scan_layers=False): one nn.scan is one kind.
    n_routed_experts: int = 0
    experts_held: int | None = None
    expert_offset: int = 0
    experts_per_token: int = 0
    expert_d_ff: int = 0
    n_shared_experts: int = 0
    routed_scaling: float = 1.0
    n_dense_layers: int = 0
    # A decoder that reads its own memory twice (models/sambay.py; the
    # published keys of Phi-4-mini-flash-reasoning): mb_per_layer > 0 makes
    # every mb_per_layer-th layer a Mamba-1 mixer in the first half of the
    # layers (and the one after it) and a Gated Memory Unit on that layer's
    # scan output in the second; the layers between attend differentially,
    # over the sliding_window newest positions in the first half, then once
    # over the whole context, then by a query alone on that one layer's K
    # and V. LayerNorm, biases on the attention projections, no rotary
    # embedding; norm_eps is the LayerNorm's. The Mamba sizes are the
    # published class's defaults (mamba_dt_rank None: ceil(d_model / 16)).
    mb_per_layer: int = 0
    sliding_window: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | None = None
    # logits = final_norm(x) E^T with E the embedding: no lm_head of its
    # own. Served in int8 the head is the embedding's transpose quantized a
    # vocabulary row (a head column), and the embedding those values
    # dequantized, so the two stay one matrix (quantize_lm_params)
    tie_embeddings: bool = False
    # The size of an attention head where the published configuration
    # states it and it is not d_model // n_heads (Falcon-H1-34B: 20 heads
    # of 128 under a hidden size of 5,120). None: the quotient.
    d_head: int | None = None
    # The type the lookup table is kept in (None: float32, cast to dtype
    # after the lookup). A served model states its compute type here: the
    # compiler turns a lookup of float32 rows that are cast afterwards into
    # a lookup in a cast table, and casts the whole table on every launch.
    embedding_dtype: "jnp.dtype | None" = None
    # A Mamba-2 mixer beside attention in every block (models/mamba2.py; the
    # published keys of Falcon-H1): mamba_n_heads > 0 makes the block's
    # mixer two branches on ONE normed input, summed:
    # x + ssm_out * Mamba2(ssm_in * u) + attention_out * Attn(attention_in * u),
    # then the MLP. mamba_n_heads heads of mamba_d_head channels with a
    # scalar decay a head, B and C of mamba_d_state states shared by
    # mamba_n_groups groups of heads, one depthwise convolution of
    # mamba_d_conv taps over x|B|C, a gated RMSNorm over each group's
    # channels; prefill runs the chunked form at mamba_chunk_size. A layer
    # then owns cached_key / cached_value AND ssm_state / conv_state.
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 128
    # The published scalar multipliers (Falcon-H1's maximal-update
    # parametrization), applied by the model where the equations have them;
    # 1.0 is the identity and adds no operation. ssm_multipliers scale the
    # five parts of the Mamba-2 input projection's result (z, x, B, C, dt),
    # mlp_multipliers the gate's input to silu and the down projection.
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: tuple = (1.0, 1.0)

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def recurrent(self) -> bool:
        """The model keeps recurrent state in its cache (a Mamba state and
        a convolution's tail): the one predicate behind every refusal of
        what cuts, rewinds or moves a cache by position."""
        return self.mb_per_layer > 0 or self.mamba_n_heads > 0

    @property
    def kv_heads_major(self) -> bool:
        """K and V cached ``(B, KV, W, D)``, a KV head's rows together, and
        not ``(B, W, KV, D)``: fewer KV heads than a sublane tile pad every
        position's ``(KV, D)`` slab to one (4 heads of bfloat16: four times
        the bytes). Only a model served from whole slots takes it: what
        cuts a cache by position reads axis 1 as positions."""
        return self.mamba_n_heads > 0 and self.kv_heads % 8 != 0

    @property
    def held_experts(self) -> int:
        if self.experts_held is None:
            return self.n_routed_experts
        return self.experts_held

    @property
    def ff_dim(self) -> int:
        return self.d_ff if self.d_ff is not None else 4 * self.d_model

    @property
    def head_dim(self) -> int:
        if self.d_head is not None:
            return self.d_head
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        kv = self.n_kv_heads if self.n_kv_heads is not None else self.n_heads
        assert self.n_heads % kv == 0, (self.n_heads, kv)
        return kv


class RMSNorm(nn.Module):
    """Root-mean-square LayerNorm (no mean subtraction), stats in float32."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


def apply_rope(x: jax.Array, theta: float, offset=0) -> jax.Array:
    """Rotary position embedding over the last axis. ``x``: (B, S, H, D).

    ``offset`` shifts the positions (may be traced) — incremental decoding
    applies rope at the token's *global* position while S == 1. A scalar
    offset shifts every row identically (generate()); a ``(B,)`` vector
    gives each batch row its OWN position, the slot-indexed decode mode
    (serve/) where co-batched requests sit at different depths.
    """
    seq_len, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    off = jnp.asarray(offset, jnp.float32)
    pos = off[..., None] + jnp.arange(seq_len, dtype=jnp.float32)
    angles = pos[..., :, None] * freqs  # (S, half) or (B, S, half)
    if off.ndim == 0:
        cos = jnp.cos(angles)[None, :, None, :]  # (1, S, 1, half)
        sin = jnp.sin(angles)[None, :, None, :]
    else:
        cos = jnp.cos(angles)[:, :, None, :]  # (B, S, 1, half)
        sin = jnp.sin(angles)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def masked_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array
) -> jax.Array:
    """Scaled-dot-product attention with an explicit boolean mask
    (broadcastable to the (B, H, Q, K) score shape) — the single copy of
    the attention math shared by training/prefill (causal mask) and cached
    decode (prefix mask).

    Scores accumulate in float32 on the MXU (``preferred_element_type``), the
    softmax runs in float32, and the context matmul ALSO accumulates f32
    (its inputs are the storage dtype — with ``kv_cache_dtype`` set that
    is the cache dtype, so without the accumulator override the attention
    output itself would round to the cache dtype, not just stored K/V)
    before returning to the query compute dtype — the TPU mixed-precision
    idiom.
    """
    d = q.shape[-1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(mask, scores, jnp.float32(-1e30))
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    ctx = jnp.einsum(
        "bhqk,bkhd->bqhd", weights, v, preferred_element_type=jnp.float32
    )
    return ctx.astype(q.dtype)


def grouped_masked_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array
) -> jax.Array:
    """GQA attention over an UN-expanded K/V: ``q`` (B, Q, H, D) against
    ``k``/``v`` (B, L, KV, D) with H a multiple of KV — the group axis is
    folded into the einsums, so the (GQA-shrunk) KV cache is read at its
    stored size instead of being ``repeat``-materialized to H heads every
    decode step. ``mask`` broadcastable to (B, 1, 1, Q, L) semantics (the
    (1, 1, 1, L) validity row the decode path builds works unchanged).
    Falls through to :func:`masked_attention` when H == KV."""
    b, qlen, h, d = q.shape
    kvh = k.shape[2]
    if kvh == h:
        return masked_attention(q, k, v, mask)
    grp = h // kvh
    q5 = q.reshape(b, qlen, kvh, grp, d)
    scores = jnp.einsum(
        "bqcgd,blcd->bcgql", q5, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(mask[:, :, None], scores, jnp.float32(-1e30))
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum(
        "bcgql,blcd->bqcgd", weights, v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype).reshape(b, qlen, h, d)


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Quantize K/V ``(B, S, H, D)`` to int8 with per-(B, S, H) float32
    scales (absmax over the head_dim vector — each stored token/head gets
    its own scale, so one outlier token cannot crush every other's
    resolution). Inverse: :func:`_dequantize_kv`."""
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.round(x32 / scale[..., None]).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """int8 cache + scales -> compute dtype. XLA fuses this elementwise
    expansion into the attention matmuls' operand reads, so HBM traffic
    per decode step stays at the int8+scale footprint (~1.06 bytes per
    cached element vs 2 bf16 / 4 f32)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _kv_quant_mode(dtype) -> str | None:
    """Storage-quantization family of a ``kv_cache_dtype`` value:
    ``"int8"`` (per-token-per-head absmax, f32 scales — ``_quantize_kv``),
    ``"int4"`` (the packed-nibble sentinel STRING — uint8 storage at
    head_dim/2 with bfloat16 scales, ``ops.quant.quantize_kv_int4``), or
    ``None`` for exact storage (f32/bf16/follow-compute). Non-sentinel
    dtype strings normalize through ``jnp.dtype`` so ``"int8"`` and
    ``jnp.int8`` configure the same cache."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        if dtype == "int4":
            return "int4"
        dtype = jnp.dtype(dtype)
    return "int8" if dtype == jnp.int8 else None


def _encode_kv(x: jax.Array, quant: str | None):
    """Storage-encode one K/V chunk for its cache's quant family —
    ``(stored, scale)`` with ``scale=None`` for exact storage. The one
    dispatch shared by the decode/prefill/paged write sites (``quant`` is
    trace-time static, from the config — never traced data)."""
    if quant == "int8":
        return _quantize_kv(x)
    if quant == "int4":
        from pytorch_distributed_training_tutorials_tpu.ops.quant import (
            quantize_kv_int4,
        )

        return quantize_kv_int4(x)
    return x, None


def _decode_kv(stored: jax.Array, scale, quant: str | None, dtype):
    """Inverse of :func:`_encode_kv` for the dense read paths (the Pallas
    paged kernel dequantizes per page tile in VMEM instead — this is its
    numerics reference). Exact storage returns the stored array as-is
    (the attention einsums promote it, preserving the pre-int4 lowering
    bit for bit)."""
    if quant == "int8":
        return _dequantize_kv(stored, scale, dtype)
    if quant == "int4":
        from pytorch_distributed_training_tutorials_tpu.ops.quant import (
            dequantize_kv_int4,
        )

        return dequantize_kv_int4(stored, scale, dtype)
    return stored


def _kv_storage(k_dtype, v_dtype, d: int):
    """Resolve a (possibly quantized, possibly string) cache dtype pair
    into concrete storage: ``(k_dtype, v_dtype, stored_head_dim,
    scale_dtype)`` with ``scale_dtype=None`` for exact storage. int4
    packs two values per uint8 byte along head_dim (``d // 2`` stored —
    ops.quant.pack_int4's half-split layout) and keeps bf16 scales so a
    token-head costs exactly half its int8 twin."""
    quant = _kv_quant_mode(k_dtype)
    if quant == "int8":
        return jnp.int8, jnp.int8, d, jnp.float32
    if quant == "int4":
        if d % 2:
            raise ValueError(f"int4 KV needs an even head_dim, got {d}")
        return jnp.uint8, jnp.uint8, d // 2, jnp.bfloat16
    if isinstance(k_dtype, str):
        k_dtype = jnp.dtype(k_dtype)
    if isinstance(v_dtype, str):
        v_dtype = jnp.dtype(v_dtype)
    return k_dtype, v_dtype, d, None


def _layer_value(var, layer):
    """What one layer reads of cache variable ``var``: the variable's own
    value, or — ``layer`` given, the variable being the whole stack the
    layer scan carries — row ``layer`` of it."""
    return var.value if layer is None else var.value[layer]


def _update_slice(var, val: jax.Array, start: tuple, layer) -> None:
    """``dynamic_update_slice`` of block ``val`` into cache variable ``var``
    at ``start`` (at ``[layer, *start]`` of a carried stack)."""
    if layer is not None:
        val, start = val[None], (layer,) + start
    var.value = jax.lax.dynamic_update_slice(var.value, val, start)


@jax.named_scope("kv_cache")
def _store_decode_kv(var, val: jax.Array, pos: jax.Array, layer=None,
                     heads_major: bool = False) -> None:
    """Write one decode chunk's per-row value ``val`` (B, S, ...) into cache
    variable ``var`` (B, max_seq_len, ...) at sequence positions
    ``pos + [0, S)`` — the one copy of the decode write used by K/V and
    their int8 scales. With ``layer`` (the scan's layer index) ``var`` is
    the carried stack ``(L, B, max_seq_len, ...)`` and the same rows land
    at ``[layer, ...]``: only the new rows are written, in place.

    Scalar ``pos`` with ``S == 1``: every row writes the same position
    (``dynamic_update_slice``, the generate() path — kept as the exact
    pre-existing lowering). Every other case — ``(B,)`` vector ``pos``
    (serve/ slot-indexed decode) and/or ``S > 1`` (suffix prefill of a
    prefix-cache hit, bucket-padded) — scatters row r's token s at
    position ``pos[r] + s``; positions outside the cache window are
    DROPPED, which is what makes parked / finished slots AND bucket
    padding past the window safe — their writes vanish instead of
    clamping onto (and corrupting) the last cache entry.

    ``heads_major``: ``var`` is ``(B, KV, max_seq_len, D)``
    (``TransformerConfig.kv_heads_major``). One index a (row, KV head,
    position), so that an update is one lane row of the cache as it lies: a
    window over the KV heads makes the compiler relay the whole stack
    around the scatter (models/sambay.py's rings, PR 34)."""
    val = val.astype(var.value.dtype)
    s = val.shape[1]
    if heads_major:
        b, kv = val.shape[0], val.shape[2]
        at = (
            jnp.arange(b)[:, None, None], jnp.arange(kv)[None, :, None],
            jnp.broadcast_to(pos, (b,))[:, None, None] + jnp.arange(s),
        )
        if layer is not None:
            at = (layer,) + at
        var.value = var.value.at[at].set(
            jnp.swapaxes(val, 1, 2), mode="drop"
        )
    elif pos.ndim == 0 and s == 1:
        _update_slice(var, val, (0, pos) + (0,) * (val.ndim - 2), layer)
    else:
        rows = jnp.arange(val.shape[0])[:, None]  # (B, 1)
        cols = (pos[:, None] if pos.ndim else pos) + jnp.arange(s)  # (B|1, S)
        at = (rows, cols) if layer is None else (layer, rows, cols)
        var.value = var.value.at[at].set(val, mode="drop")


@jax.named_scope("kv_cache")
def _store_prefill_kv(var, val: jax.Array, layer=None) -> None:
    """Write a prefill's ``val`` (B, S, ...) into cache variable ``var`` at
    positions ``[0, S)`` (``[layer, :, 0:S]`` of a carried stack)."""
    _update_slice(var, val.astype(var.value.dtype), (0,) * val.ndim, layer)


@jax.named_scope("kv_cache")
def _store_cache_index(var, new: jax.Array, layer=None) -> None:
    """Set a layer's ``cache_index`` (its row of the carried ``(L,)`` /
    ``(L, B)`` stack when ``layer`` is given)."""
    var.value = new if layer is None else var.value.at[layer].set(new)


def _gather_pages(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Materialize each row's logical window from the shared page pool —
    the REFERENCE read path (``cfg.paged_kernel=False``), and the
    numerics oracle the fused kernel pins against.

    ``pool`` is ``(kv_pages, page_size, ...)``; ``table`` is the per-row
    page-table ``(B, P)`` of int32 page ids (``P * page_size`` = the
    logical window). Returns ``(B, P * page_size, ...)`` — exactly the
    array the whole-slot decode path reads, which is why THIS path's
    paged attention is bitwise the unpaged one: the gather feeds the
    SAME grouped_masked_attention over the SAME validity mask, and
    unbacked entries (the sentinel id ``kv_pages``, out of range) fill
    with 0.0, which the mask already excludes (a masked column
    contributes an exact softmax zero — see the decode-branch comment
    below). With ``cfg.paged_kernel=True`` this dense window is never
    built: ``ops.paged_attention`` streams page tiles through an
    online-softmax accumulator instead, trading the bitwise-to-unpaged
    guarantee for float-tolerance (greedy token-exact) equivalence and
    no ``(B, W, ...)`` temporary.

    Page ids are traced DATA: ``jnp.take`` with ``mode="fill"``, never a
    Python branch (graftcheck ``traced-control-flow`` has the fixture
    pair pinning this idiom)."""
    out = jnp.take(pool, table, axis=0, mode="fill", fill_value=0)
    b, p = table.shape
    return out.reshape((b, p * pool.shape[1]) + pool.shape[2:])


@jax.named_scope("kv_cache")
def _store_paged_kv(
    var, table: jax.Array, val: jax.Array, pos, layer=None
) -> None:
    """Paged twin of :func:`_store_decode_kv`: write row r's token s of
    ``val`` (B, S, ...) into pool variable ``var`` (kv_pages, page_size,
    ...) at the page/offset the row's ``table`` (B, P) maps logical
    position ``pos[r] + s`` to. With ``layer``, ``var`` is the carried
    stack of pools ``(L, kv_pages, page_size, ...)`` (``table`` is still
    this layer's) and the rows land in pool ``layer``.

    Logical positions past the table (bucket padding beyond the window)
    and positions whose table entry is the sentinel ``kv_pages`` (parked
    or unbacked rows) both resolve to an out-of-range page id and DROP —
    the same safety rule as the unpaged scatter. The engine parks a
    finished slot by sentinel-filling its table row, so an inactive
    slot's junk writes land nowhere even after its pages are recycled."""
    val = val.astype(var.value.dtype)
    s = val.shape[1]
    lead = 0 if layer is None else 1
    n_pages, page_size = var.value.shape[lead], var.value.shape[lead + 1]
    p_cap = table.shape[1]
    # pos is (B,) by construction (paged decode always runs slot-indexed)
    cols = pos[:, None] + jnp.arange(s)  # (B, S) logical positions
    p_idx = cols // page_size
    offs = cols % page_size
    ids = jnp.take_along_axis(
        table, jnp.clip(p_idx, 0, p_cap - 1), axis=1
    )
    ids = jnp.where(p_idx < p_cap, ids, n_pages)  # past-window -> OOB
    at = (ids, offs) if layer is None else (layer, ids, offs)
    var.value = var.value.at[at].set(val, mode="drop")


def _is_cache_index(path) -> bool:
    """Is this tree_map_with_path leaf a ``cache_index`` counter?"""
    key = path[-1]
    return str(getattr(key, "key", getattr(key, "idx", key))) == "cache_index"


def rewind_cache_index(cache, steps):
    """Roll every ``cache_index`` counter in a decode ``cache`` tree back
    by per-row ``steps`` — the speculative-verify rewind (serve/engine.py,
    models/generate.py ``speculative_k``): a ``(B, k+1)`` verify chunk
    advances the counters by ``k+1``, but only ``1 + n_accept`` of those
    K/V entries (the chunk's first input plus the accepted draft tokens)
    are real, so the counters step back by ``k - n_accept``.

    Only the COUNTERS move; the rejected positions' K/V entries stay in
    the cache as stale rows. That is safe by construction: the next
    decode chunk writes ``k+1`` fresh positions starting at the rewound
    counter, which covers every stale position before any query can
    attend to it (stale entries sit at ``[new_pos, old_pos)`` and
    ``new_pos + k >= old_pos - 1`` always), and the validity mask bounds
    reads at the query's own position meanwhile. ``steps`` is ``(B,)``
    (broadcasting over the leading layer axis of ``scan_layers``-stacked
    ``(L, B)`` counters) or a scalar."""

    def upd(path, leaf):
        if _is_cache_index(path):
            return leaf - jnp.asarray(steps, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(upd, cache)


def park_cache_index(cache, parked, window: int):
    """Set every ``cache_index`` counter of the rows where ``parked``
    ``(B,)`` is true to ``window``, one past the last position: the depth
    at which a row's writes already drop (``_store_decode_kv``) and which
    ``ops.decode_attention`` reads as "nothing here", so a slot that holds
    no live sequence costs a decode step no rows of its cache. The serving
    chain (``ServeEngine._chain_impl``) applies it before every step to the
    slots without budget; a refill writes the slot's real depth
    (``serve.slots.write_slot``). ``parked`` broadcasts over the leading
    layer axis of ``scan_layers``-stacked ``(L, B)`` counters."""

    def upd(path, leaf):
        if _is_cache_index(path):
            return jnp.where(parked, jnp.asarray(window, leaf.dtype), leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(upd, cache)


def widen_cache_index(cache, n_rows: int):
    """Widen scalar ``cache_index`` counters to per-row ``(n_rows,)``
    vectors (trailing axis — ``(L,) -> (L, n_rows)`` under
    ``scan_layers``), leaving every other leaf alone. The decode path
    branches on the counters' trace-time rank (see ``Attention``), so
    this flips a freshly prefilled ``generate()``-layout cache into the
    slot-indexed layout where each batch row decodes at its OWN depth —
    what ``generate(..., speculative_k=...)`` needs once per-row accepted
    lengths diverge (serve/ builds its state in this layout from the
    start, :func:`..serve.slots.init_slot_state`)."""

    def upd(path, leaf):
        if _is_cache_index(path):
            return jnp.broadcast_to(
                leaf[..., None], leaf.shape + (n_rows,)
            ).astype(jnp.int32)
        return leaf

    return jax.tree_util.tree_map_with_path(upd, cache)


def _expand_kv(kv: jax.Array, n_heads: int) -> jax.Array:
    """Repeat grouped K/V heads up to the query head count (GQA -> MHA
    view); identity when the counts already match."""
    reps = n_heads // kv.shape[2]
    if reps == 1:
        return kv
    return jnp.repeat(kv, reps, axis=2)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Dense causal softmax attention; (B, S, H, D) in and out."""
    s = q.shape[1]
    mask = jnp.tril(jnp.ones((s, s), jnp.bool_))[None, None, :, :]
    return masked_attention(q, k, v, mask)


class LoRADelta(nn.Module):
    """Stacked multi-tenant LoRA delta for ONE base projection.

    Declares the whole bank as two params — ``lora_a`` ``(n_adapters,
    d_in, rank)`` and ``lora_b`` ``(n_adapters, rank, d_out)`` — and
    returns each batch row's low-rank delta ``(x @ A[id]) @ B[id]``,
    gathering the row's factors by its adapter id inside the compiled
    program (:func:`..adapters.bank.apply_lora`; ``jnp.take``, never a
    Python branch on the traced id). Zero init is a contract, not a
    convenience: adapter 0 IS the base model, and unregistered rows stay
    exactly zero, so their delta is an exact ``0.0`` and base-tenant
    outputs are token-identical to a LoRA-free build. Scaling (alpha) is
    folded into ``lora_b`` by the training side — no separate knob here.
    """

    n_adapters: int
    rank: int
    d_in: int
    d_out: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, adapter_ids):
        from pytorch_distributed_training_tutorials_tpu.adapters.bank import (
            apply_lora,
        )

        a = self.param(
            "lora_a", nn.initializers.zeros,
            (self.n_adapters, self.d_in, self.rank),
        )
        b = self.param(
            "lora_b", nn.initializers.zeros,
            (self.n_adapters, self.rank, self.d_out),
        )
        return apply_lora(x, a, b, adapter_ids, dtype=self.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig

    def _cache_vars(self, b: int, k_dtype, v_dtype):
        """The one copy of the KV-cache schema shared by the decode and
        prefill branches (shapes/dtypes must agree or decode misreads what
        prefill wrote). Only ``kv_heads`` heads are cached (GQA);
        ``cfg.kv_cache_dtype`` overrides the storage dtype (long-window
        decode is cache-traffic-bound — see the config field). int8
        storage additionally carries per-(batch, position, head) float32
        scales (absmax over head_dim — the same per-channel scheme
        ops.quant uses for weights); scale vars are ``None`` otherwise.

        The ``heads`` axis here is ALSO the tensor-parallel shard axis
        for sharded serving (ISSUE 15): k/v_proj are column-parallel
        (head-split) under TP_RULES/INT8_TP_RULES, so their activations
        arrive head-sharded and the cache stores them without any
        collective. The model body deliberately has NO
        with_sharding_constraint — GSPMD propagates the layout from the
        committed params + cache operands, and the serving engine pins
        its cache trees at the jit boundaries
        (``parallel.tensor_parallel.SLOT_STATE_RULES`` names these leaf
        paths; ``ServeEngine._pin``). Renaming a cache variable here
        breaks that rule table — keep them in sync.

        Under ``scan_layers`` the tree gains a leading layer axis
        (``cached_key`` ``(L, B, S, KV, D)``, ``cache_index`` ``(L,)`` or
        ``(L, B)``). An apply that creates the cache declares THIS layer's
        variables here and the scan stacks them; an apply that was handed
        the cache carries the stack through the scan, so the variables
        returned here (they exist: the initializers below do not run) ARE
        the stack, and ``__call__`` writes and reads them at its
        ``layer`` index (``_store_decode_kv`` / ``_layer_value``). Same
        paths, shapes and dtypes either way."""
        cfg = self.cfg
        h, d = cfg.kv_heads, cfg.head_dim
        if cfg.kv_cache_dtype is not None:
            k_dtype = v_dtype = cfg.kv_cache_dtype
        k_dtype, v_dtype, d_store, scale_dtype = _kv_storage(
            k_dtype, v_dtype, d
        )
        shape = (b, cfg.max_seq_len, h, d_store)
        if cfg.kv_heads_major:  # a KV head's rows together
            shape = (b, h, cfg.max_seq_len, d_store)
        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros, shape, k_dtype,
        )
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros, shape, v_dtype,
        )
        idx = self.variable(
            "cache", "cache_index",
            lambda: jnp.zeros((), jnp.int32),
        )
        k_scale = v_scale = None
        if scale_dtype is not None:
            k_scale = self.variable(
                "cache", "cached_key_scale",
                jnp.zeros, (b, cfg.max_seq_len, h), scale_dtype,
            )
            v_scale = self.variable(
                "cache", "cached_value_scale",
                jnp.zeros, (b, cfg.max_seq_len, h), scale_dtype,
            )
        return cached_k, cached_v, idx, k_scale, v_scale

    def _paged_cache_vars(self, b: int, k_dtype, v_dtype):
        """Paged twin of :meth:`_cache_vars` (``cfg.kv_pages`` > 0,
        decode only): K/V live in ONE shared ``(kv_pages, kv_page_size,
        kv_heads, head_dim)`` pool with NO batch axis — only the
        ``page_table`` ``(b, P)`` (P = max_seq_len // kv_page_size,
        sentinel-initialized to the OOB id ``kv_pages``) and the per-row
        ``cache_index`` ``(b,)`` carry batch. That asymmetry is the
        point: a batch-1 splice/prefill apply writes DIRECTLY into the
        shared pool through its own one-row table (serve/engine.py), so
        prefix-cache hits pin pages instead of copying segments. int8
        storage carries per-(page, offset, head) float32 scale pools —
        the same per-token-per-head absmax scheme as the unpaged cache
        (``_quantize_kv``), just paged storage. Under tensor-parallel
        serving the pool leaves shard on the same ``kv_heads`` axis as
        the flat cache (SLOT_STATE_RULES ``paged_*`` rules): page-table
        gathers index the page axis, which stays replicated, so a
        gather/scatter never crosses shards (ISSUE 15)."""
        cfg = self.cfg
        h, d = cfg.kv_heads, cfg.head_dim
        if cfg.kv_cache_dtype is not None:
            k_dtype = v_dtype = cfg.kv_cache_dtype
        k_dtype, v_dtype, d_store, scale_dtype = _kv_storage(
            k_dtype, v_dtype, d
        )
        npages, psize = cfg.kv_pages, cfg.kv_page_size
        if psize < 1 or cfg.max_seq_len % psize:
            raise ValueError(
                f"kv_page_size {psize} must be >= 1 and divide "
                f"max_seq_len {cfg.max_seq_len}"
            )
        cached_k = self.variable(
            "cache", "paged_key",
            jnp.zeros, (npages, psize, h, d_store), k_dtype,
        )
        cached_v = self.variable(
            "cache", "paged_value",
            jnp.zeros, (npages, psize, h, d_store), v_dtype,
        )
        n_tables = cfg.max_seq_len // psize
        table = self.variable(
            "cache", "page_table",
            lambda: jnp.full((b, n_tables), npages, jnp.int32),
        )
        idx = self.variable(
            "cache", "cache_index",
            lambda: jnp.zeros((b,), jnp.int32),
        )
        k_scale = v_scale = None
        if scale_dtype is not None:
            k_scale = self.variable(
                "cache", "paged_key_scale",
                jnp.zeros, (npages, psize, h), scale_dtype,
            )
            v_scale = self.variable(
                "cache", "paged_value_scale",
                jnp.zeros, (npages, psize, h), scale_dtype,
            )
        return cached_k, cached_v, table, idx, k_scale, v_scale

    @nn.compact
    def __call__(
        self, x, decode: bool = False, prefill: bool = False,
        adapter_ids=None, layer=None, stacks=None,
    ):
        # ``layer``: this layer's index when the layer scan carries the
        # stacked cache (TransformerLM) — every cache variable is then the
        # whole stack, written and read at ``[layer]``; None = own variables.
        # ``stacks``: under the layer scan of a quantized model, each
        # projection's ``(stacked Int8Param, layer index)`` by its name
        cfg = self.cfg
        stacks = stacks or {}
        assert not (decode and prefill), "decode and prefill are exclusive"
        h, kv, d = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        if cfg.quantized:
            from pytorch_distributed_training_tutorials_tpu.ops.quant import (
                Int8DenseGeneral,
            )

            # Megatron layout: q/k/v column-split over heads, o row-split
            # (its input arrives head-sharded) with one psum per branch
            proj = lambda name, heads: functools.partial(  # noqa: E731
                Int8DenseGeneral(
                    (heads, d), axis=-1, use_bias=False, name=name,
                    mesh=cfg.tp_mesh, shard_kind="column",
                ),
                stacked=stacks.get(name),
            )
            out_proj = functools.partial(
                Int8DenseGeneral(
                    cfg.d_model, axis=(-2, -1), use_bias=False, name="o_proj",
                    mesh=cfg.tp_mesh, shard_kind="row",
                ),
                stacked=stacks.get("o_proj"),
            )
        else:
            proj = lambda name, heads: nn.DenseGeneral(  # noqa: E731
                (heads, d), axis=-1, use_bias=False, dtype=cfg.dtype,
                name=name,
            )
            out_proj = nn.DenseGeneral(
                cfg.d_model, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
                name="o_proj",
            )
        q_raw = proj("q_proj", h)(x)
        k_raw = proj("k_proj", kv)(x)  # GQA: only kv_heads cached/projected
        v = proj("v_proj", kv)(x)
        # W (c u) = c (W u): the published scalars on the branch's input
        # and on its keys multiply the projections' results
        if cfg.attention_in_multiplier != 1.0:
            q_raw = q_raw * cfg.attention_in_multiplier
            v = v * cfg.attention_in_multiplier
        if cfg.attention_in_multiplier * cfg.key_multiplier != 1.0:
            k_raw = k_raw * (cfg.attention_in_multiplier * cfg.key_multiplier)
        if cfg.lora_adapters:
            # per-row LoRA deltas on the raw projections (id 0 / any
            # unregistered row adds an exact 0.0 — see LoRADelta)
            lora = lambda name, dout: LoRADelta(  # noqa: E731
                cfg.lora_adapters, cfg.lora_rank, cfg.d_model, dout,
                dtype=cfg.dtype, name=name,
            )
            q_raw = q_raw + lora("q_proj_lora", h * d)(
                x, adapter_ids
            ).reshape(q_raw.shape)
            k_raw = k_raw + lora("k_proj_lora", kv * d)(
                x, adapter_ids
            ).reshape(k_raw.shape)
            v = v + lora("v_proj_lora", kv * d)(
                x, adapter_ids
            ).reshape(v.shape)

        if decode and cfg.kv_pages:
            # paged decode (cfg.kv_pages > 0): K/V land in the shared
            # pool through the per-row page table (traced data —
            # _store_paged_kv / _gather_pages document the sentinel/drop
            # safety rules). Two read paths, selected ENGINE-STATICALLY
            # by cfg.paged_kernel (a config bool — Python control flow on
            # trace-time structure, never on a traced value):
            # - gather (default, the numerics reference): materialize the
            #   window dense and run the same grouped attention as the
            #   unpaged branch — bitwise the unpaged decode.
            # - kernel: ops.paged_attention walks the table inside a
            #   Pallas online-softmax kernel; no dense window exists,
            #   float-tolerance (token-exact greedy) vs the gather path.
            b, s = x.shape[0], x.shape[1]
            cached_k, cached_v, table, idx, k_scale, v_scale = (
                self._paged_cache_vars(b, k_raw.dtype, v.dtype)
            )
            quant = _kv_quant_mode(cfg.kv_cache_dtype)
            # (B,) — paged decode is always slot-indexed
            pos = _layer_value(idx, layer)
            tbl = _layer_value(table, layer)
            q = apply_rope(q_raw, cfg.rope_theta, offset=pos)
            k = apply_rope(k_raw, cfg.rope_theta, offset=pos)
            k_q, k_s = _encode_kv(k, quant)
            v_q, v_s = _encode_kv(v, quant)
            _store_paged_kv(cached_k, tbl, k_q, pos, layer)
            _store_paged_kv(cached_v, tbl, v_q, pos, layer)
            if quant:
                _store_paged_kv(k_scale, tbl, k_s, pos, layer)
                _store_paged_kv(v_scale, tbl, v_s, pos, layer)
            _store_cache_index(idx, pos + s, layer)
            k_pool = _layer_value(cached_k, layer)
            v_pool = _layer_value(cached_v, layer)
            ks_pool = _layer_value(k_scale, layer) if quant else None
            vs_pool = _layer_value(v_scale, layer) if quant else None
            if cfg.paged_kernel:
                from pytorch_distributed_training_tutorials_tpu.ops.paged_attention import (  # noqa: E501
                    paged_attention,
                )

                out = paged_attention(
                    q, k_pool, v_pool, tbl, pos,
                    k_scale=ks_pool, v_scale=vs_pool, quant=quant,
                )
            else:
                with jax.named_scope("kv_cache"):
                    k_read = _decode_kv(
                        _gather_pages(k_pool, tbl),
                        _gather_pages(ks_pool, tbl) if quant else None,
                        quant, k.dtype,
                    )
                    v_read = _decode_kv(
                        _gather_pages(v_pool, tbl),
                        _gather_pages(vs_pool, tbl) if quant else None,
                        quant, v.dtype,
                    )
                qpos = pos[..., None] + jnp.arange(s)
                valid = (
                    jnp.arange(cfg.max_seq_len) <= qpos[..., :, None]
                )  # (B, S, max_len): per-slot depths, like the unpaged path
                out = grouped_masked_attention(
                    q, k_read, v_read, valid[:, None, :, :]
                )
        elif decode:
            # incremental decoding: S tokens in (S == 1 for the classic
            # generate()/serve step; S > 1 is a CHUNKED continuation — the
            # suffix prefill of a prefix-cache hit, serve/engine.py), KV
            # appended to the cache at positions pos + [0, S), attention
            # over the cache prefix. Cache tensors are zero-init on the
            # first (shape-init) apply and thereafter carry state.
            # Contract: the caller keeps REAL positions under max_seq_len
            # (generate() enforces; serve/ admission-checks) — writes past
            # the window (bucket padding) drop in _store_decode_kv.
            # Note decode always uses this dense cached path — a custom
            # cfg.attention_fn (ring/Ulysses) governs training/prefill
            # only; a *non-equivalent* attention_fn (e.g. sliding window)
            # would need its own decode rule.
            b, s = x.shape[0], x.shape[1]
            cached_k, cached_v, idx, k_scale, v_scale = self._cache_vars(
                b, k_raw.dtype, v.dtype
            )
            # cache_index is () for generate() (one shared position) or
            # (B,) for slot-indexed serving (serve/: each slot decodes at
            # its own depth); apply_rope, _store_decode_kv, and the
            # validity mask all branch on the trace-time rank
            pos = _layer_value(idx, layer)
            quant = _kv_quant_mode(cfg.kv_cache_dtype)
            q = apply_rope(q_raw, cfg.rope_theta, offset=pos)
            k = apply_rope(k_raw, cfg.rope_theta, offset=pos)
            k_q, k_s = _encode_kv(k, quant)  # quantized: store q + scale
            v_q, v_s = _encode_kv(v, quant)
            major = cfg.kv_heads_major
            _store_decode_kv(cached_k, k_q, pos, layer, major)
            _store_decode_kv(cached_v, v_q, pos, layer, major)
            if quant:
                _store_decode_kv(k_scale, k_s, pos, layer)
                _store_decode_kv(v_scale, v_s, pos, layer)
            _store_cache_index(idx, pos + s, layer)
            from pytorch_distributed_training_tutorials_tpu.ops.decode_attention import (  # noqa: E501
                decode_attention,
                decode_block,
            )

            if (
                s == 1 and quant is None and cfg.tp_mesh is None
                and decode_block(
                    cfg.max_seq_len, kv, d, cached_k.value.dtype
                )
            ):
                # a step, a cache stored as floats, no mesh, tiles the
                # kernel takes: K and V are read where they lie, in the
                # carried stack, up to each row's own depth; a row whose
                # depth is the window (park_cache_index) reads nothing
                with jax.named_scope("decode_attn"):
                    k_all, v_all = cached_k.value, cached_v.value
                    out = decode_attention(
                        q[:, 0],
                        k_all if layer is not None else k_all[None],
                        v_all if layer is not None else v_all[None],
                        0 if layer is None else layer,
                        jnp.broadcast_to(pos, (b,)),
                        heads_major=major,
                    )[:, None]
            else:
                # everything else (a chunk of several positions, int8 and
                # int4 storage, tensor-parallel serving, head widths that
                # are no whole lane tiles): plain einsums over a copy of
                # the layer's window
                with jax.named_scope("kv_cache"):
                    k_read = _decode_kv(
                        _layer_value(cached_k, layer),
                        _layer_value(k_scale, layer) if quant else None,
                        quant, k.dtype,
                    )
                    v_read = _decode_kv(
                        _layer_value(cached_v, layer),
                        _layer_value(v_scale, layer) if quant else None,
                        quant, v.dtype,
                    )
                    if major:
                        # (B, KV, W, D) -> positions before heads, and in
                        # float32: this is the path of toy widths (whole
                        # tiles take the kernel), and the CPU backend has no
                        # bfloat16 product of this form inside the layer loop
                        k_read = jnp.swapaxes(k_read, 1, 2).astype(jnp.float32)
                        v_read = jnp.swapaxes(v_read, 1, 2).astype(jnp.float32)
                        q = q.astype(jnp.float32)
                # attend over the whole cache: query token i (global
                # position pos + i) masks positions beyond pos + i — same
                # math as training/prefill (a masked-out cache column
                # contributes an exact softmax zero, so window-vs-prompt-
                # sized reductions agree bitwise). GQA: the cache holds
                # kv_heads and is read UN-expanded (grouped einsums) —
                # per-step cache traffic scales with n_kv_heads, the point
                # of the layout
                qpos = (pos[..., None] if pos.ndim else pos) + jnp.arange(s)
                valid = (
                    jnp.arange(cfg.max_seq_len) <= qpos[..., :, None]
                )  # (S, max_len) shared — or (B, S, max_len) per slot
                if valid.ndim == 2:
                    valid = valid[None]
                out = grouped_masked_attention(
                    q, k_read, v_read,
                    valid[:, None, :, :],
                ).astype(x.dtype)
        else:
            q = apply_rope(q_raw, cfg.rope_theta)
            k = apply_rope(k_raw, cfg.rope_theta)
            if prefill:
                # batched prefill: the same causal forward as training, but
                # it also populates cache positions [0, S) and sets
                # cache_index = S, so decode=True steps continue from the
                # prompt in O(1) launches instead of O(P) one-token passes
                # (generate() drives this; the one-token path self-documents
                # the contract)
                b, s = x.shape[0], x.shape[1]
                cached_k, cached_v, idx, k_scale, v_scale = self._cache_vars(
                    b, k_raw.dtype, v.dtype
                )
                quant = _kv_quant_mode(cfg.kv_cache_dtype)
                k_q, k_s = _encode_kv(k, quant)  # quantized cache: q+scale
                v_q, v_s = _encode_kv(v, quant)
                if cfg.kv_heads_major:
                    k_q, v_q = jnp.swapaxes(k_q, 1, 2), jnp.swapaxes(v_q, 1, 2)
                _store_prefill_kv(cached_k, k_q, layer)
                _store_prefill_kv(cached_v, v_q, layer)
                if quant:
                    _store_prefill_kv(k_scale, k_s, layer)
                    _store_prefill_kv(v_scale, v_s, layer)
                _store_cache_index(idx, jnp.asarray(s, jnp.int32), layer)
            attn = (
                cfg.attention_fn
                if cfg.attention_fn is not None
                else causal_attention
            )
            div = getattr(attn, "requires_seq_divisible", 0)
            if not decode and not prefill:
                # tag for remat_policy="dots_attn": saveable across the
                # block's checkpoint boundary (training path only — the
                # serving paths never differentiate)
                from jax.ad_checkpoint import checkpoint_name

                attn_inner = attn

                def attn(q_, k_, v_, _inner=attn_inner):
                    return checkpoint_name(_inner(q_, k_, v_), "attn_out")
            if prefill and div and x.shape[1] % div:
                # sequence-parallel schedules (ring/Ulysses) require the
                # sequence to divide the seq mesh axis; for prompt lengths
                # that don't, prefill falls back to the causal-equivalent
                # dense path (the cache contents, raw K/V, are
                # attention-independent either way). Divisible prompts —
                # the long-context case SP exists for — keep the SP
                # schedule and its memory bound; other custom fns (e.g.
                # the Pallas flash kernel) handle any length. (ADVICE r3)
                attn = causal_attention
            from pytorch_distributed_training_tutorials_tpu.ops.flash_attention import (  # noqa: E501
                flash_attention_forward,
                prefill_takes_kernel,
            )

            # a prefill's attention sits under one scope on both forms, so
            # that the device trace says which ran and what it cost
            with (
                jax.named_scope("prefill_attn") if prefill
                else contextlib.nullcontext()
            ):
                if (
                    prefill and cfg.attention_fn is None
                    and cfg.tp_mesh is None
                    and prefill_takes_kernel(x.shape[1], d)
                ):
                    # the model's own causal attention, no mesh, heads of
                    # whole lane tiles, a length from which the kernel
                    # wins: the flash forward over the K and V just
                    # computed, at their stored head count (no score
                    # matrix, no copy of K and V to the query heads). The
                    # cache's storage is no condition: it was written
                    # above either way
                    out = flash_attention_forward(q, k, v)
                else:
                    # a user's attention_fn, a mesh, toy head widths, short
                    # prompts, training: the dense form, the kernel's
                    # reference. GQA: attention_fns keep their (B, S, H, D)
                    # contract — K/V repeat up to the query head count here
                    # (the cache, when prefilling, stores the UN-repeated
                    # kv heads)
                    out = attn(q, _expand_kv(k, h), _expand_kv(v, h))
        y = out_proj(out)
        if cfg.attention_out_multiplier != 1.0:
            y = y * cfg.attention_out_multiplier
        if cfg.lora_adapters:
            # o_proj delta reads the flattened attention context — same
            # (H*D -> d_model) contraction as the base row-parallel matmul
            flat = out.reshape(out.shape[0], out.shape[1], h * d)
            y = y + LoRADelta(
                cfg.lora_adapters, cfg.lora_rank, h * d, cfg.d_model,
                dtype=cfg.dtype, name="o_proj_lora",
            )(flat, adapter_ids)
        return y


def _latent_prefill_attention(q, k, v) -> jax.Array:
    """Causal attention with q, k (B, S, H, nope + rope) and v (B, S, H,
    v) through the flash kernel (128 heads of dense scores at S = 2048 are
    2.1 GB). The kernel's q, k and v share one width that is a whole number
    of lane tiles: all three are padded with zeros to it (192 and 128 ->
    256; the zeros add nothing to a score or an output), and q is scaled so
    that the kernel's ``1 / sqrt(width)`` comes out as ``1 / sqrt(nope +
    rope)``."""
    from pytorch_distributed_training_tutorials_tpu.ops.flash_attention import (
        flash_attention,
    )

    d, dv = q.shape[-1], v.shape[-1]
    width = -(-max(d, dv) // 128) * 128

    def pad(t):
        return jnp.pad(t, ((0, 0),) * 3 + ((0, width - t.shape[-1]),))

    q = (q.astype(jnp.float32) * (width / d) ** 0.5).astype(q.dtype)
    return flash_attention(pad(q), pad(k), pad(v))[..., :dv]


class LatentUp(nn.Module):
    """``W_ukv`` of latent attention, (kv_lora_rank, heads * (nope + v)),
    columns a head at a time ``[k_nope | v]``: float ``kernel`` or int8
    ``q`` with ``scale`` a column. Two uses of the one matrix:
    :meth:`project` up-projects latents (prefill), :meth:`absorbed` hands
    the matrix itself out for decode, which multiplies the query and the
    output by it and never the cache."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        shape = (
            cfg.kv_lora_rank,
            cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim),
        )
        if cfg.quantized:
            self.q = self.param("q", nn.initializers.zeros, shape, jnp.int8)
            self.scale = self.param(
                "scale", nn.initializers.ones, (1, shape[1]), jnp.float32
            )
        else:
            self.kernel = self.param(
                "kernel", nn.initializers.lecun_normal(), shape
            )

    def project(self, c: jax.Array, stacked=None) -> jax.Array:
        """``c`` (B, S, rank) -> ``[k_nope | v]`` (B, S, H, nope + v).
        ``stacked``: as :func:`..ops.quant._int8_affine` takes it."""
        cfg = self.cfg
        c2 = c.reshape(-1, c.shape[-1])
        if cfg.quantized:
            from pytorch_distributed_training_tutorials_tpu.ops.quant import (
                Int8Param,
                int8_matmul,
            )

            out = int8_matmul(
                c2, *(stacked or (Int8Param(q=self.q, scale=self.scale),))
            )
        else:
            out = c2.astype(cfg.dtype) @ self.kernel.astype(cfg.dtype)
        return out.astype(c.dtype).reshape(
            *c.shape[:-1], cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim
        )

    def absorbed(self, dtype) -> tuple[jax.Array, jax.Array]:
        """``(w_uk (rank, H, nope), w_uv (rank, H, v))`` in ``dtype``."""
        cfg = self.cfg
        if cfg.quantized:
            w = (self.q.astype(jnp.float32) * self.scale).astype(dtype)
        else:
            w = self.kernel.astype(dtype)
        w = w.reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
        return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim :]


class LatentAttention(nn.Module):
    """Multi-head latent attention (``cfg.kv_lora_rank > 0``)::

        c_q = RMSNorm(x W_dq);  [q_nope | q_rope] = c_q W_uq   a head
        [c_kv | k_r] = x W_dkv; c = RMSNorm(c_kv); k_rope = RoPE(k_r)
        [k_nope | v] = c W_ukv                                 a head
        softmax((q_nope . k_nope + RoPE(q_rope) . k_rope) / sqrt(nope + rope)) v

    ``k_rope`` is one vector a token, shared by all heads. The cache holds
    ``[c | k_rope]`` a token (``cached_latent`` (B, max_seq_len, rank +
    rope rounded up to whole lane tiles), under ``scan_layers`` with the
    leading layer axis of PR 28's carried stack) and ``cache_index``. Two paths, chosen by what the apply
    is: without ``decode`` (training, prefill) K and V are up-projected a
    head and attended blockwise (the flash kernel); with ``decode`` (a cache
    handed in; one token or a chunk) the cache itself is read,
    ``q_nope W_uk^T`` against ``c`` and ``p c`` times ``W_uv``: the same
    sums in another order, without ever up-projecting the cached tokens.
    """

    cfg: TransformerConfig

    def _stored_width(self) -> int:
        """A token's row of the cache: ``[c | k_rope]`` and zeros up to a
        whole number of 128-wide lane tiles (576 -> 640). The chip's tiled
        memory pads a 576-wide minor axis to 640 anyway, unless the
        compiler turns the sequence axis minor-most, and then the carried
        stack is copied whole into and out of the layer scan on every
        step (seen in the program compiled for a v5e, PR 30)."""
        cfg = self.cfg
        return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128

    def _row(self, c, k_rope):
        """``[c | k_rope | 0...]`` as the cache stores a token."""
        pad = self._stored_width() - c.shape[-1] - k_rope.shape[-1]
        parts = [c, k_rope.astype(c.dtype)]
        if pad:
            parts.append(jnp.zeros(c.shape[:-1] + (pad,), c.dtype))
        return jnp.concatenate(parts, -1)

    def _cache_vars(self, b: int, dtype):
        cfg = self.cfg
        if cfg.kv_cache_dtype is not None:
            if _kv_quant_mode(cfg.kv_cache_dtype):
                raise ValueError(
                    "a latent cache is stored as floats: kv_cache_dtype "
                    f"{cfg.kv_cache_dtype!r} is not supported"
                )
            dtype = jnp.dtype(cfg.kv_cache_dtype)
        latent = self.variable(
            "cache", "cached_latent", jnp.zeros,
            (b, cfg.max_seq_len, self._stored_width()), dtype,
        )
        idx = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        return latent, idx

    @nn.compact
    def __call__(
        self, x, decode: bool = False, prefill: bool = False,
        adapter_ids=None, layer=None, stacks=None,
    ):
        cfg = self.cfg
        stacks = stacks or {}  # as Attention's
        assert not (decode and prefill), "decode and prefill are exclusive"
        h, rank = cfg.n_heads, cfg.kv_lora_rank
        nope, rope_d, vd = (
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        )
        if cfg.quantized:
            from pytorch_distributed_training_tutorials_tpu.ops.quant import (
                Int8Dense,
            )

            dense = lambda f, name: functools.partial(  # noqa: E731
                Int8Dense(f, use_bias=False, name=name),
                stacked=stacks.get(name),
            )
        else:
            dense = lambda f, name: nn.Dense(  # noqa: E731
                f, use_bias=False, dtype=cfg.dtype, name=name
            )
        b, s = x.shape[0], x.shape[1]
        c_q = RMSNorm(cfg.norm_eps, name="q_norm")(
            dense(cfg.q_lora_rank, "q_down")(x)
        )
        q = dense(h * (nope + rope_d), "q_up")(c_q).reshape(
            b, s, h, nope + rope_d
        )
        kv = dense(rank + rope_d, "kv_down")(x)
        c = RMSNorm(cfg.norm_eps, name="kv_norm")(kv[..., :rank])
        up = LatentUp(cfg, name="kv_up")
        out_proj = dense(cfg.d_model, "o_proj")
        scale = (nope + rope_d) ** -0.5

        if decode:
            latent_var, idx = self._cache_vars(b, x.dtype)
            pos = _layer_value(idx, layer)  # () generate, (B,) serve slots
            q_rope = apply_rope(q[..., nope:], cfg.rope_theta, offset=pos)
            k_rope = apply_rope(
                kv[..., None, rank:], cfg.rope_theta, offset=pos
            )[:, :, 0]
            _store_decode_kv(latent_var, self._row(c, k_rope), pos, layer)
            _store_cache_index(idx, pos + s, layer)
            with jax.named_scope("latent_attn"):
                from pytorch_distributed_training_tutorials_tpu.ops.latent_attention import (  # noqa: E501
                    latent_decode_attention,
                    latent_decode_attention_reference,
                )

                w_uk, w_uv = up.absorbed(x.dtype)
                q_lat = jnp.einsum(
                    "bshd,rhd->bshr", q[..., :nope], w_uk,
                    preferred_element_type=jnp.float32,
                )
                # one contraction over a row as the cache holds it,
                # [c | k_rope | 0...]: slicing the cache would copy it
                q_full = self._row(q_lat, q_rope).astype(
                    latent_var.value.dtype
                )
                if s == 1:
                    # a step: the kernel reads the rows where they lie, in
                    # the carried stack, up to each row's own depth
                    stack = latent_var.value
                    o_lat = latent_decode_attention(
                        q_full[:, 0],
                        stack if layer is not None else stack[None],
                        0 if layer is None else layer,
                        jnp.broadcast_to(pos, (b,)), sm_scale=scale,
                    )[:, None]
                else:
                    # a chunk of positions: the same sums as plain einsums
                    # over a copy of the layer's rows
                    with jax.named_scope("kv_cache"):
                        lat = _layer_value(latent_var, layer)
                    qpos = (pos[..., None] if pos.ndim else pos) + jnp.arange(s)
                    valid = jnp.arange(cfg.max_seq_len) <= qpos[..., :, None]
                    o_lat = latent_decode_attention_reference(
                        q_full, lat, valid if valid.ndim == 3 else valid[None],
                        sm_scale=scale,
                    )
                out = jnp.einsum(
                    "bshr,rhd->bshd", o_lat[..., :rank].astype(x.dtype), w_uv,
                    preferred_element_type=jnp.float32,
                ).astype(x.dtype)
        else:
            q_rope = apply_rope(q[..., nope:], cfg.rope_theta)
            k_rope = apply_rope(kv[..., None, rank:], cfg.rope_theta)
            if prefill:
                latent_var, idx = self._cache_vars(b, x.dtype)
                _store_prefill_kv(
                    latent_var, self._row(c, k_rope[:, :, 0]), layer
                )
                _store_cache_index(idx, jnp.asarray(s, jnp.int32), layer)
            with jax.named_scope("latent_attn"):
                k_v = up.project(c, stacks.get("kv_up"))  # (B, S, H, nope + v)
                k = jnp.concatenate(
                    [k_v[..., :nope],
                     jnp.broadcast_to(k_rope, (b, s, h, rope_d))], -1
                )
                q_full = jnp.concatenate([q[..., :nope], q_rope], -1)
                out = _latent_prefill_attention(q_full, k, k_v[..., nope:])
        return out_proj(out.reshape(b, s, h * vd))


class SwiGLU(nn.Module):
    cfg: TransformerConfig
    d_ff: int | None = None  # None: cfg.ff_dim (a shared expert gives its own)

    @nn.compact
    def __call__(self, x, adapter_ids=None, stacks=None):
        cfg = self.cfg
        stacks = stacks or {}  # as Attention's
        ff_dim = self.d_ff if self.d_ff is not None else cfg.ff_dim
        if cfg.quantized:
            from pytorch_distributed_training_tutorials_tpu.ops.quant import Int8Dense

            # gate/up column-split over d_ff, down row-split (Megatron MLP)
            dense = lambda f, name, kind: functools.partial(  # noqa: E731
                Int8Dense(
                    f, use_bias=False, name=name,
                    mesh=cfg.tp_mesh, shard_kind=kind,
                ),
                stacked=stacks.get(name),
            )
        else:
            dense = lambda f, name, kind: nn.Dense(  # noqa: E731
                f, use_bias=False, dtype=cfg.dtype, name=name
            )
        gate_pre = dense(ff_dim, "gate_proj", "column")(x)
        up = dense(ff_dim, "up_proj", "column")(x)
        if cfg.lora_adapters:
            lora = lambda name, din, dout: LoRADelta(  # noqa: E731
                cfg.lora_adapters, cfg.lora_rank, din, dout,
                dtype=cfg.dtype, name=name,
            )
            gate_pre = gate_pre + lora(
                "gate_proj_lora", cfg.d_model, ff_dim
            )(x, adapter_ids)
            up = up + lora(
                "up_proj_lora", cfg.d_model, ff_dim
            )(x, adapter_ids)
        gate_mult, down_mult = cfg.mlp_multipliers
        if gate_mult != 1.0:
            gate_pre = gate_pre * gate_mult
        hidden = nn.silu(gate_pre) * up
        y = dense(cfg.d_model, "down_proj", "row")(hidden)
        if cfg.lora_adapters:
            y = y + lora(
                "down_proj_lora", ff_dim, cfg.d_model
            )(hidden, adapter_ids)
        if down_mult != 1.0:
            y = y * down_mult
        return y


def _remat_policy(cfg: TransformerConfig):
    """Resolve ``cfg.remat_policy`` to a jax.checkpoint policy (or None =
    recompute everything). Unknown names fail loud.

    ``"dots_attn"`` additionally saves the attention output (tagged
    ``attn_out`` below) — with a Pallas flash kernel the attention is a
    custom call, not a dot, so plain ``"dots"`` recomputes the whole flash
    FORWARD inside the backward pass; saving its (B, S, H, D) output
    trades ~16 MB/layer (350m, B=4) for one fewer kernel invocation per
    layer per step (round 5 measured the win)."""
    if cfg.remat_policy is None:
        return None
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.remat_policy == "dots_attn":
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names("attn_out"),
        )
    raise ValueError(
        f"unknown remat_policy {cfg.remat_policy!r} "
        "(None, 'dots', or 'dots_attn')"
    )


def _check_new_block_fields(cfg: TransformerConfig) -> None:
    """Refuse, in words, the combinations latent attention, the dropless
    experts and the layers with recurrent state do not run with yet."""
    if cfg.tie_embeddings and not cfg.mb_per_layer:
        raise ValueError(
            "tie_embeddings is the head of the layout mb_per_layer gives "
            "(models/sambay.py); the other layouts keep an lm_head of "
            "their own"
        )
    if cfg.mamba_n_heads and cfg.mb_per_layer:
        raise ValueError(
            "mamba_n_heads (a Mamba-2 mixer beside attention in every "
            "block) and mb_per_layer (Mamba-1 and attention layers by "
            "their place) are two layouts: set one"
        )
    if cfg.mb_per_layer:
        if cfg.mb_per_layer != 2 or cfg.n_layers % 4 or cfg.n_layers < 8:
            raise ValueError(
                "mb_per_layer lays the layers out in periods of two (a "
                "Mamba or Gated Memory Unit layer, then an attention "
                "layer) around the two middle layers: it must be 2, and "
                f"n_layers a multiple of 4 from 8 up; got mb_per_layer="
                f"{cfg.mb_per_layer}, n_layers={cfg.n_layers}"
            )
        if cfg.sliding_window < 1 or not cfg.tie_embeddings:
            raise ValueError(
                "mb_per_layer needs sliding_window >= 1 (the window "
                "layers' ring of K and V) and tie_embeddings=True (the "
                "published head is the embedding)"
            )
        if cfg.n_heads % 2 or cfg.kv_heads % 2:
            raise ValueError(
                "differential attention pairs neighbouring heads: n_heads "
                f"{cfg.n_heads} and n_kv_heads {cfg.kv_heads} must be even"
            )
    if cfg.mamba_n_heads:
        if (cfg.mamba_d_head < 1 or cfg.mamba_n_groups < 1
                or cfg.mamba_n_heads % cfg.mamba_n_groups
                or cfg.mamba_chunk_size < 1 or cfg.mamba_d_conv < 2):
            raise ValueError(
                "mamba_n_heads needs mamba_d_head >= 1, mamba_n_groups "
                "dividing it, mamba_chunk_size >= 1 and mamba_d_conv >= 2; "
                f"got heads {cfg.mamba_n_heads} of {cfg.mamba_d_head}, "
                f"{cfg.mamba_n_groups} groups, chunk {cfg.mamba_chunk_size}, "
                f"{cfg.mamba_d_conv} taps"
            )
        if len(cfg.ssm_multipliers) != 5 or len(cfg.mlp_multipliers) != 2:
            raise ValueError(
                "ssm_multipliers are five (z, x, B, C, dt) and "
                "mlp_multipliers two (gate, down)"
            )
    if cfg.recurrent:
        if not cfg.scan_layers:
            raise ValueError(
                "a model with recurrent state (mb_per_layer, mamba_n_heads) "
                "always runs its layers scanned with its cache stacked a "
                "layer (models/sambay.py, models/mamba2.py): say so with "
                "scan_layers=True, which is what serve.slots.write_slot reads"
            )
        if _kv_quant_mode(cfg.kv_cache_dtype):
            raise ValueError(
                "a model with recurrent state keeps its K and V (rings, "
                "shared and whole caches) as floats: kv_cache_dtype "
                f"{cfg.kv_cache_dtype!r} is not supported"
            )
        for field, what in (
            ("kv_pages", "a paged KV cache (a page holds heads of K and V "
                         "at absolute positions: neither a state nor a ring)"),
            ("tp_mesh", "tensor-parallel serving (the slot rules shard K "
                        "and V by head and know no state leaf)"),
            ("lora_adapters", "LoRA adapters"),
            ("attention_fn", "a custom attention_fn"),
            ("moe_experts", "the capacity-dropping MoEFFN"),
            ("n_routed_experts", "the dropless routed experts"),
            ("kv_lora_rank", "latent attention"),
            ("remat", "remat (training through the selective scan, the "
                      "chunked form or ssd_update)"),
        ):
            if getattr(cfg, field) not in (None, 0, False):
                raise ValueError(
                    f"layers with recurrent state (mb_per_layer, "
                    f"mamba_n_heads) do not run with {what} ({field})"
                )
    if cfg.latent:
        if not (cfg.q_lora_rank and cfg.qk_nope_head_dim
                and cfg.qk_rope_head_dim and cfg.v_head_dim):
            raise ValueError(
                "latent attention (kv_lora_rank > 0) needs q_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim"
            )
        for field, what in (
            ("kv_pages", "a paged KV cache"),
            ("tp_mesh", "tensor-parallel serving"),
            ("lora_adapters", "LoRA adapters"),
            ("attention_fn", "a custom attention_fn"),
        ):
            if getattr(cfg, field) not in (None, 0):
                raise ValueError(
                    f"latent attention does not run with {what} ({field}): "
                    "its cache is one latent a token, not heads of K and V"
                )
    if cfg.n_routed_experts:
        if cfg.moe_experts:
            raise ValueError(
                "n_routed_experts (dropless) and moe_experts (capacity "
                "dropping) are two expert layers: set one"
            )
        if not (0 < cfg.experts_per_token <= cfg.n_routed_experts
                and cfg.expert_d_ff > 0):
            raise ValueError(
                "n_routed_experts needs experts_per_token in "
                "[1, n_routed_experts] and expert_d_ff"
            )
        if not (0 < cfg.held_experts
                and cfg.expert_offset + cfg.held_experts <= cfg.n_routed_experts):
            raise ValueError(
                f"experts_held {cfg.held_experts} from expert_offset "
                f"{cfg.expert_offset} do not lie in the router's "
                f"{cfg.n_routed_experts}"
            )
        if not 0 <= cfg.n_dense_layers < cfg.n_layers:
            raise ValueError(
                "n_dense_layers must leave at least one layer of experts"
            )
        if cfg.scan_layers and cfg.n_dense_layers:
            raise ValueError(
                "scan_layers=True scans layers of one kind: a model with "
                f"n_dense_layers={cfg.n_dense_layers} leading dense layers "
                "before its layers of routed experts runs unrolled "
                "(scan_layers=False)"
            )
        if cfg.lora_adapters or (
            cfg.quantized and cfg.tp_mesh is not None
        ):
            raise ValueError(
                "the dropless expert layer runs with neither LoRA adapters "
                "nor tensor-parallel int8 serving"
            )


class Block(nn.Module):
    cfg: TransformerConfig
    # this layer's feed-forward is the routed experts (with the shared
    # one) and not the dense SwiGLU: cfg.n_routed_experts > 0 and the
    # layer is not one of the cfg.n_dense_layers leading ones
    routed: bool = False

    @nn.compact
    def __call__(
        self, x, decode: bool = False, prefill: bool = False,
        adapter_ids=None, layer=None, stacks=None, p_len=None,
    ):
        # ``p_len`` ((B,), a prefill of a model with recurrent state): the
        # rows' real lengths inside the right-padded bucket, where the
        # state stops
        cfg = self.cfg
        stacks = stacks or {}  # by submodule, as Attention's by projection
        attention = LatentAttention if cfg.latent else Attention
        u = RMSNorm(cfg.norm_eps, name="attn_norm")(x)
        y = attention(cfg, name="attn")(
            u, decode=decode, prefill=prefill, adapter_ids=adapter_ids,
            layer=layer, stacks=stacks.get("attn"),
        )
        if cfg.mamba_n_heads:
            # the parallel mixer: a Mamba-2 branch on the same normed input
            from pytorch_distributed_training_tutorials_tpu.models.mamba2 import (  # noqa: E501
                Mamba2Mixer,
            )

            y = y + Mamba2Mixer(cfg, name="mamba")(
                u, decode=decode, prefill=prefill, p_len=p_len, layer=layer,
                stacks=stacks.get("mamba"),
            )
        if cfg.sandwich_norm:
            y = RMSNorm(cfg.norm_eps, name="post_attn_norm")(y)
        x = x + y
        m = RMSNorm(cfg.norm_eps, name="mlp_norm")(x)
        if self.routed:
            y = RoutedExperts(
                n_routed=cfg.n_routed_experts, held=cfg.held_experts,
                offset=cfg.expert_offset, top_k=cfg.experts_per_token,
                d_ff=cfg.expert_d_ff, scaling=cfg.routed_scaling,
                dtype=cfg.dtype, quantized=cfg.quantized, name="moe",
            )(m)
            if cfg.n_shared_experts:
                with jax.named_scope("moe_shared"):
                    y = y + SwiGLU(
                        cfg, d_ff=cfg.n_shared_experts * cfg.expert_d_ff,
                        name="shared",
                    )(m, stacks=stacks.get("shared"))
        elif cfg.moe_experts > 0:
            # MoE blocks carry no LoRA hooks (TransformerLM rejects the
            # combination up front)
            y = MoEFFN(
                num_experts=cfg.moe_experts,
                top_k=cfg.moe_top_k,
                d_ff=cfg.ff_dim,
                capacity_factor=cfg.moe_capacity_factor,
                dtype=cfg.dtype,
                group_size=cfg.moe_group_size,
                name="moe",
            )(m)
        else:
            y = SwiGLU(cfg, name="mlp")(m, adapter_ids, stacks.get("mlp"))
        if cfg.sandwich_norm:
            y = RMSNorm(cfg.norm_eps, name="post_mlp_norm")(y)
        return x + y


class _ScanCell(nn.Module):
    """``Block`` adapted to ``nn.scan``'s (carry, out) contract."""

    cfg: TransformerConfig
    decode: bool = False
    prefill: bool = False
    routed: bool = False

    @nn.compact
    def __call__(self, x, ids, stacks, layers, p_len=None):
        # ``ids`` is the scan's nn.broadcast input: the per-row adapter-id
        # vector handed WHOLE to every layer (None when lora is off — an
        # empty pytree, so the scanned program is unchanged). ``stacks`` is
        # broadcast too: the int8 weights as the scan's parameters hold
        # them, stacked (None unless quantized). ``layers`` is the scanned
        # layer index, once for the cache when the scan carries it and
        # once for the weights when there are ``stacks``, else None (also
        # an empty pytree). ``p_len`` is broadcast like ``ids`` (Block's;
        # None but for a prefill of a model with recurrent state)
        layer, w_layer = layers
        if stacks is not None:
            from pytorch_distributed_training_tutorials_tpu.ops.quant import (
                Int8Param,
            )

            stacks = jax.tree_util.tree_map(
                lambda w: (w, w_layer), stacks,
                is_leaf=lambda t: isinstance(t, Int8Param),
            )
        return Block(self.cfg, self.routed, name="block")(
            x, decode=self.decode, prefill=self.prefill, adapter_ids=ids,
            layer=layer, stacks=stacks, p_len=p_len,
        ), None


def _int8_stacks(block):
    """The ``{'q', 'scale'}`` pairs of the scanned block's stacked
    parameters as ``Int8Param`` under their modules' names, everything
    else left out: what ``int8_matmul`` reads in place at a layer index, so
    that ``lax.scan``'s slice of each layer's weights is dead (it was a
    read and a write of every weight byte before the kernel read the
    copy). None where there are none (``init``). The stacks of a routed
    layer's experts (rank 4) stay with the slice."""
    from pytorch_distributed_training_tutorials_tpu.ops.quant import Int8Param

    def walk(tree):
        if "q" in tree and "scale" in tree:
            q = tree["q"]
            if q.dtype == jnp.int8 and q.ndim == 3:
                return Int8Param(q=q, scale=tree["scale"])
            return None
        out = {k: walk(v) for k, v in tree.items() if hasattr(v, "keys")}
        return {k: v for k, v in out.items() if v is not None} or None

    return walk(block)


class TransformerLM(nn.Module):
    """Causal LM: tokens (B, S) int32 -> logits (B, S, vocab).

    ``return_hidden=True`` stops before the lm_head and returns the
    final-norm hidden states (B, S, d_model) instead — the seam the fused
    logits-free loss (:mod:`..ops.fused_loss`) trains through.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self,
        tokens,
        decode: bool = False,
        prefill: bool = False,
        return_hidden: bool = False,
        last_pos=None,
        adapter_ids=None,
    ):
        cfg = self.cfg
        if cfg.quantized and cfg.moe_experts:
            raise ValueError(
                "quantized serving does not support the capacity-dropping "
                "MoEFFN (moe_experts): the experts int8 serving runs are "
                "the dropless ones (n_routed_experts)"
            )
        _check_new_block_fields(cfg)
        if tokens.shape[1] > cfg.max_seq_len:
            raise ValueError(
                f"sequence length {tokens.shape[1]} exceeds "
                f"max_seq_len {cfg.max_seq_len}"
            )
        if cfg.mb_per_layer:
            # Mamba, window, full, Gated Memory Unit and cross-attention
            # layers by their place: a model of its own under this name
            from pytorch_distributed_training_tutorials_tpu.models import (
                sambay,
            )

            if adapter_ids is not None:
                raise ValueError(
                    "layers with recurrent state run with no LoRA adapters"
                )
            return sambay.forward(
                self, tokens, decode, prefill, return_hidden, last_pos
            )
        if adapter_ids is not None and not cfg.lora_adapters:
            raise ValueError(
                "adapter_ids passed but cfg.lora_adapters == 0 — build "
                "with TransformerConfig(lora_adapters=N, lora_rank=r)"
            )
        if cfg.lora_adapters:
            if cfg.moe_experts:
                raise ValueError(
                    "LoRA adapters support dense blocks only (no MoE)"
                )
            # the adapter id is DATA (a traced per-row vector — scalar ids
            # broadcast over the batch); rows default to the base adapter
            ids = jnp.broadcast_to(
                jnp.asarray(
                    0 if adapter_ids is None else adapter_ids, jnp.int32
                ),
                (tokens.shape[0],),
            )
        else:
            ids = None
        x = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
            param_dtype=cfg.embedding_dtype or jnp.float32, name="tok_emb",
        )(tokens)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        p_len = None
        if cfg.mamba_n_heads:
            if decode and tokens.shape[1] != 1:
                raise ValueError(
                    "a model with recurrent state steps one position at a "
                    f"time: a decode chunk of {tokens.shape[1]} positions "
                    "(suffix or chunked prefill, a speculative verify) has "
                    "no state to rewind to"
                )
            if prefill:
                # a right-padded bucket: the state stops at the row's real
                # length (the last_pos a bucketed prefill is told)
                p_len = jnp.broadcast_to(jnp.asarray(
                    tokens.shape[1] if last_pos is None
                    else jnp.asarray(last_pos) + 1, jnp.int32,
                ), (tokens.shape[0],))
        # the layers from routed_from on have the routed experts for their
        # feed-forward, the leading ones the dense SwiGLU
        routed_from = cfg.n_dense_layers if cfg.n_routed_experts else cfg.n_layers
        if cfg.scan_layers:
            cell = _ScanCell
            if cfg.remat:
                cell = nn.remat(
                    cell, prevent_cse=False, policy=_remat_policy(cfg)
                )
            # An apply that was HANDED a cache carries the stacked tree
            # through the scan whole: each layer gets its index, writes only
            # its new rows at [layer] (in place on the carried buffer) and
            # reads cache[layer] (Attention's ``layer``). Scanning over the
            # cache instead makes it a scanned input AND a scanned output —
            # two buffers — so every layer's whole slice is copied out and
            # stacked back on every step. An apply that CREATES its cache
            # (init, a prefill with no cache in, the eval_shape protos) has
            # to scan over it, because flax cannot create a variable in a
            # carried collection inside the scan; the stacked tree it
            # returns has the same paths, shapes and dtypes.
            carry_cache = "layers" in self.variables.get("cache", {})
            # int8 weights are read where they lie in the stacked
            # parameters, so the kernel is handed the stacks whole and the
            # layer's index. Under a tensor-parallel mesh the layers keep
            # the slice (_int8_affine: int8_matmul_tp's shard_map, a bare
            # pallas_call cannot sit on a stack GSPMD has sharded)
            stacks = None
            if cfg.quantized and cfg.tp_mesh is None:
                stacks = _int8_stacks(
                    self.variables.get("params", {}).get("layers", {})
                    .get("block", {})
                )
            axes = {"params": 0, "losses": 0}
            if not carry_cache:
                axes["cache"] = 0
            stack = nn.scan(
                cell,
                # 'losses' rides along axis 0 so per-layer sown values (MoE
                # load balancing) survive the scan instead of being dropped;
                # the adapter-id vector (or None) broadcasts to every layer
                variable_axes=axes,
                variable_carry="cache" if carry_cache else False,
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast, 0, nn.broadcast),
                length=cfg.n_layers,
            )(cfg, decode, prefill, routed_from == 0, name="layers")
            # the scope marks what lax.scan itself does around the cell:
            # it slices every stacked leaf (each layer's float weights,
            # norms and scales, a scanned cache's slice) out by the layer
            # index and stacks a scanned cache back. No line of the program does that, so no narrower
            # scope (weights_slice / kv_cache) can be put on it; an op under
            # layer_scan and not under the cell's "layers" is that slicing.
            # (A carried cache's reads and writes are the program's own
            # lines, under layers/block/attn/kv_cache.)
            with jax.named_scope("layer_scan"):
                index = lambda on: (  # noqa: E731
                    jnp.arange(cfg.n_layers) if on else None
                )
                x, _ = stack(
                    x, ids, stacks,
                    (index(carry_cache), index(stacks is not None)), p_len,
                )
        else:
            # decode/prefill are Python bools steering cache behavior — they
            # must stay static under remat (args 2/3 of __call__ incl. self)
            block_cls = (
                nn.remat(
                    Block, static_argnums=(2, 3), policy=_remat_policy(cfg)
                )
                if cfg.remat
                else Block
            )
            for i in range(cfg.n_layers):
                routed = i >= routed_from
                if ids is None:
                    x = block_cls(cfg, routed, name=f"block_{i}")(
                        x, decode, prefill
                    )
                else:
                    # adapter_ids is positional arg 4 — TRACED (remat's
                    # static_argnums stays (2, 3): decode/prefill only)
                    x = block_cls(cfg, routed, name=f"block_{i}")(
                        x, decode, prefill, ids
                    )
        if prefill or (decode and last_pos is not None):
            # only the last position's logits feed the next-token sample;
            # skip the (P-1) discarded lm_head rows — at serving widths the
            # head is the single largest matmul in the prefill
            if last_pos is None:
                x = x[:, -1:]
            else:
                # bucketed prefill (serve/): prompts arrive right-padded to
                # a static bucket length, so the next-token logits must be
                # gathered at each row's LAST REAL prompt position (traced,
                # per row) rather than the padding tail. Causal attention
                # makes positions [0, P) independent of what follows, so
                # the gathered hidden state equals the unpadded prefill's.
                # The decode=True variant is the chunked SUFFIX prefill of
                # a prefix-cache hit (serve/engine.py): ``last_pos`` is the
                # LOCAL index of the last real suffix token. Scalar or
                # per-row (B,) vector both work — the broadcast below is
                # the whole plumbing. decode with last_pos=None keeps the
                # full (B, S, V) logits — the generate()/serve chain
                # contract (S == 1), and ALSO what the speculative verify
                # forward rides on: a (B, k+1) chunk needs every
                # position's logits to judge the k draft tokens
                # (speculative_accept, models/sampling.py).
                lp = jnp.broadcast_to(
                    jnp.asarray(last_pos, jnp.int32), (x.shape[0],)
                )
                x = x[jnp.arange(x.shape[0]), lp][:, None]
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        if return_hidden:
            # the fused-loss seam: final-norm hidden states (B, S, d_model),
            # lm_head NOT applied — ops.fused_loss streams them against the
            # lm_head kernel blockwise so the (B, S, vocab) logits never
            # materialize (train.trainer loss="fused_cross_entropy"). The
            # lm_head param still exists (init runs without this flag);
            # grads reach it through the fused op, not this module.
            return x
        if cfg.quantized:
            from pytorch_distributed_training_tutorials_tpu.ops.quant import Int8Dense

            logits = Int8Dense(
                cfg.vocab_size, use_bias=False, name="lm_head",
                mesh=cfg.tp_mesh, shard_kind="column",
            )(x)
        else:
            logits = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                name="lm_head",
            )(x)
        if cfg.lm_head_multiplier != 1.0:
            logits = logits * jnp.asarray(cfg.lm_head_multiplier, logits.dtype)
        return logits


# Megatron-style tensor-parallel layout over the 'model' mesh axis:
# column-split the head/ff output dims of q/k/v/gate/up, row-split the
# input dims of o_proj/down_proj (one allreduce per residual branch),
# vocab-split the LM head; embeddings replicated. Specs shorter than a
# param's rank are left-padded with None (covers nn.scan's leading layer
# axis). Consumed by parallel.tensor_parallel.TensorParallel.
# `(^|/)`-anchored so top-LEVEL params match too: paths are rooted at the
# tree the consumer walks — "params/lm_head/kernel" in a variables tree but
# bare "lm_head/kernel" in a params-only tree (audit, quantized loads); a
# bare `.*/` prefix silently missed the latter and left lm_head replicated.
TP_RULES: list[tuple[str, P]] = [
    (r"(^|/)(q_proj|k_proj|v_proj)/kernel$", P(None, "model", None)),
    (r"(^|/)o_proj/kernel$", P("model", None, None)),
    (r"(^|/)(gate_proj|up_proj)/kernel$", P(None, "model")),
    (r"(^|/)down_proj/kernel$", P("model", None)),
    (r"(^|/)tok_emb/embedding$", P(None, None)),
    (r"(^|/)lm_head/kernel$", P(None, "model")),
]


def ep_rules() -> list[tuple[str, P]]:
    """TP + expert-parallel rules for an MoE transformer (dp x tp x ep)."""
    return MOE_RULES + TP_RULES


# The int8 analog of TP_RULES for the {'q', 'scale'} serving layout (all
# kernels stored flattened 2-D (in, out) by Int8Dense/Int8DenseGeneral):
# column-parallel layers split q AND their per-output-column scales on the
# output dim; row-parallel layers split q on the input dim and replicate
# scales (each shard's partial is already scale-multiplied before the psum
# — ops.quant.int8_matmul_tp). Embeddings/norms stay replicated float, the
# mixed layout the reference's cell-4 param audit shows
# (/root/reference/03.model_parallel.ipynb:409).
def int8_param_sharding(path: str, ndim: int, mesh):
    """The one place INT8_TP_RULES turns into a placement: NamedSharding
    for one serving-tree leaf (float leaves fall through to replicated).
    Shared by :func:`load_quantized_lm`'s streaming placement and by
    :func:`place_int8_lm_params` (and the dryrun's certification of both)."""
    from jax.sharding import NamedSharding

    from pytorch_distributed_training_tutorials_tpu.parallel.tensor_parallel import (
        spec_for_path,
    )

    return NamedSharding(
        mesh, spec_for_path(path, ndim, INT8_TP_RULES, mesh=mesh)
    )


def place_int8_lm_params(params, mesh):
    """Place an in-memory int8 serving tree (:func:`quantize_lm_params`
    output) onto ``mesh`` per :data:`INT8_TP_RULES`."""
    from pytorch_distributed_training_tutorials_tpu.utils.tree import keystr

    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: jax.device_put(
            leaf,
            int8_param_sharding(
                keystr(kp), getattr(leaf, "ndim", 0), mesh
            ),
        ),
        params,
    )


INT8_TP_RULES: list[tuple[str, P]] = [
    (
        r"(^|/)(q_proj|k_proj|v_proj|gate_proj|up_proj|lm_head)/q$",
        P(None, "model"),
    ),
    (
        r"(^|/)(q_proj|k_proj|v_proj|gate_proj|up_proj|lm_head)/scale$",
        P(None, "model"),
    ),
    (r"(^|/)(o_proj|down_proj)/q$", P("model", None)),
    (r"(^|/)(o_proj|down_proj)/scale$", P(None, None)),
]


# the matmul weights int8 serving replaces (embeddings + norms stay float —
# the exact mixed layout the reference's cell-4 param audit shows)
_QUANTIZED_KERNELS = frozenset(
    {
        "q_proj", "k_proj", "v_proj", "o_proj",
        "gate_proj", "up_proj", "down_proj", "lm_head",
        # latent attention (2-D kernels, the first axis contracted)
        "q_down", "q_up", "kv_down", "kv_up",
        # models/sambay.py: the fused projections, the Mamba mixer's and
        # the Gated Memory Unit's (all 2-D)
        "qkv_proj", "gate_up_proj", "in_proj", "x_proj", "dt_proj",
        "out_proj",
    }
)
# the stacks of a dropless expert layer ("moe": bare (experts, in, out)
# arrays): int8 with one scale a column of each expert; its router stays
# float32
_QUANTIZED_EXPERT_STACKS = frozenset({"w_gate", "w_up", "w_down"})


def quantize_lm_params(params, embedding_dtype=None):
    """Convert trained f32 :class:`TransformerLM` params into the
    ``quantized=True`` serving layout: every matmul ``kernel`` becomes
    ``{'q': int8, 'scale': f32 per-column}`` (DenseGeneral kernels
    flattened 2-D), norms/embeddings untouched.

    The ``from_pretrained(load_in_8bit=True)`` conversion step, done
    explicitly: pairs with :func:`..parallel.auto.load_quantized` (which
    streams + quantizes a checkpoint leaf-by-leaf) when the checkpoint is
    on disk, or runs directly on in-memory params. Handles both layer
    layouts: unrolled (``block_i/...``) and ``scan_layers=True``
    (``layers/block/...`` — kernels carry a leading layer axis and are
    quantized per layer, so every layer gets its own scales;
    ``quantize(stack(f32)) == stack(quantize(f32))`` exactly, pinned by
    ``tests/test_int8_serving.py``).

    A model without an ``lm_head`` has tied embeddings (models/sambay.py):
    its head becomes the embedding's transpose in int8 with one scale a
    vocabulary row, and its embedding those values dequantized, cast to
    ``embedding_dtype`` (the model's compute dtype, which is what the
    quantized model keeps its lookup table in; None: float32).
    """
    from pytorch_distributed_training_tutorials_tpu.ops.quant import quantize_int8

    from collections.abc import Mapping

    def walk(tree, stacked=False):
        out = {}
        for name, sub in tree.items():
            if (
                name in _QUANTIZED_KERNELS
                and isinstance(sub, Mapping)  # dict or flax FrozenDict
                and "kernel" in sub
            ):
                out[name] = {
                    **_quantize_kernel(
                        name, sub["kernel"], quantize_int8, stacked=stacked
                    ),
                    **{k: v for k, v in sub.items() if k != "kernel"},
                }
            elif isinstance(sub, Mapping):
                # under the nn.scan stack ("layers"), kernels carry a
                # leading (n_layers,) axis that must not be mistaken for
                # the contraction dim ("layers_a", "layers_b": the two
                # scans of models/sambay.py)
                out[name] = walk(
                    sub, stacked=stacked or name.startswith("layers")
                )
            elif name in _QUANTIZED_EXPERT_STACKS and "router" in tree:
                # (..., experts, in, out): the reduction is over "in" alone,
                # so the layer axis of a stack needs no loop
                qp = quantize_int8(sub, channel_axis=-1, reduce_axis=-2)
                out[name] = {"q": qp.q, "scale": qp.scale}
            else:
                out[name] = sub
        return out

    out = walk(dict(params))
    if "lm_head" not in out and "tok_emb" in out:
        # tied embeddings: the head is the embedding's transpose, one scale
        # a vocabulary row, and the embedding those int8 values dequantized
        qp = quantize_int8(jnp.asarray(out["tok_emb"]["embedding"]).T)
        out["lm_head"] = {"q": qp.q, "scale": qp.scale.reshape(1, -1)}
        table = (qp.q.astype(jnp.float32) * out["lm_head"]["scale"]).T
        out["tok_emb"] = {
            "embedding": table.astype(embedding_dtype or jnp.float32)
        }
    return out


def stack_quantized_lm_params(params):
    """Convert an unrolled quantized serving tree (``block_0`` ..
    ``block_{L-1}``) into the ``scan_layers=True`` layout
    (``layers/block/...`` with a leading layer axis on every leaf).

    Why: the unrolled serving graph contains L copies of the block body;
    the scanned graph contains one. That makes compile time and executable
    size O(1) in depth (what the larger program costs per launch on the
    chip: not measured). Parity with
    the reference's ``device_map="auto"`` serving path (SURVEY C13) is
    unchanged — same weights, same math, one program shape.

    Float leaves (norms) stack the same way; per-layer int8 scales are
    exactly the per-layer quantization (``quantize(stack) ==
    stack(quantize)``). Serve with ``dataclasses.replace(cfg,
    quantized=True, scan_layers=True)``. For tensor-parallel serving,
    re-place the stacked tree (:func:`place_int8_lm_params`) — the
    INT8_TP_RULES specs left-pad ``None`` over the new leading axis.
    """
    blocks = {}
    rest = {}
    for name, sub in dict(params).items():
        if name.startswith("block_"):
            blocks[int(name[len("block_"):])] = sub
        else:
            rest[name] = sub
    if not blocks:
        raise ValueError(
            "no block_<i> subtrees found — already stacked, or not a "
            "TransformerLM serving tree"
        )
    n = len(blocks)
    if sorted(blocks) != list(range(n)):
        raise ValueError(f"non-contiguous block indices: {sorted(blocks)}")
    ordered = [blocks[i] for i in range(n)]
    if len({jax.tree_util.tree_structure(b) for b in ordered}) > 1:
        raise ValueError(
            "the blocks are of more than one kind (leading dense layers "
            "before layers of routed experts): one scan stacks one kind, "
            "serve such a model unrolled"
        )
    rest["layers"] = {
        "block": jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *ordered
        )
    }
    return rest


def _quantize_kernel(name: str, kernel, quantize_int8, stacked=False) -> dict:
    """One matmul kernel -> {'q', 'scale'} in the serving layout (2-D
    flattened the way Int8DenseGeneral stores it).

    The input/output axis split is keyed by the TransformerLM layer name:
    ``o_proj`` is the one axis=(-2, -1) projection ((H, D, d_model) ->
    inputs are the leading axes); everything else contracts its first axis.
    Adding a new name to ``_QUANTIZED_KERNELS`` requires deciding its split
    here — an unknown name is NOT quantized (it passes through as float),
    so a mistake fails loud (missing 'q' param), never silently wrong.

    ``stacked``: the kernel carries a leading ``(n_layers,)`` scan axis;
    each layer is quantized independently (per-layer scales), matching
    what ``nn.scan`` slices per iteration.
    """
    kern = jnp.asarray(kernel)
    if stacked:
        if kern.ndim < 3:
            raise ValueError(f"{name}: stacked kernel rank {kern.ndim} < 3")
        qs, scales = [], []
        for l in range(kern.shape[0]):
            part = _quantize_kernel(name, kern[l], quantize_int8)
            qs.append(part["q"])
            scales.append(part["scale"])
        return {"q": jnp.stack(qs), "scale": jnp.stack(scales)}
    if kern.ndim < 2:
        raise ValueError(f"{name}: kernel rank {kern.ndim} < 2")
    if name == "o_proj":
        k2 = kern.reshape(-1, kern.shape[-1])  # (H*D, d_model)
    else:
        k2 = kern.reshape(kern.shape[0], -1)  # (in, out...)
    qp = quantize_int8(k2)
    return {"q": qp.q, "scale": qp.scale.reshape(1, -1)}


def load_quantized_lm(path, mesh=None, *, materialize=True):
    """Stream a trained f32 :class:`TransformerLM` checkpoint straight into
    the ``quantized=True`` serving layout, one leaf at a time.

    Handles both layer layouts: unrolled (``block_i/...``) and
    ``scan_layers=True`` checkpoints (kernels under ``layers/`` carry a
    leading layer axis and are quantized per layer).

    ``materialize=False`` skips the terminal
    :func:`..utils.tree.device_materialize` pass — for callers that
    assemble or transform several loaded subtrees and materialize the
    final tree once (``examples/serve_llm_int8.py``); anything consumed
    directly should keep the default (jit re-uploads host numpy
    arguments on every call).

    The full ``from_pretrained(..., load_in_8bit=True)`` loop (reference
    ``03.model_parallel.ipynb`` cell 2, SURVEY C13) on the flagship model:
    each kernel is restored (:func:`..parallel.auto.restore_leaf` — no other
    IO), flattened, quantized, and freed before the next leaf is read, so
    the f32 model is never resident on host. Serve with
    ``TransformerLM(replace(cfg, quantized=True))`` and
    :func:`..models.generate.generate`.

    With ``mesh`` (a ``{'model': M, ...}`` mesh), every quantized leaf is
    placed onto devices per :data:`INT8_TP_RULES` (float leaves replicate)
    as soon as it is produced — the ``device_map="auto"`` + 8-bit + *bigger
    than one chip* combination: host peak stays one-leaf-bounded AND no
    device ever holds more than its 1/M shard of the int8 weights. Pass
    ``dataclasses.replace(cfg, quantized=True, tp_mesh=mesh)`` to serve.
    """
    import orbax.checkpoint as ocp

    from pytorch_distributed_training_tutorials_tpu.ops.quant import quantize_int8
    from pytorch_distributed_training_tutorials_tpu.parallel.auto import (
        checkpoint_leaf_metadata,
        restore_leaf,
    )

    def place(keys: list[str], leaf):
        if mesh is None:
            return leaf
        return jax.device_put(
            leaf,
            int8_param_sharding(
                "/".join(keys), getattr(leaf, "ndim", 0), mesh
            ),
        )

    flat, _ = checkpoint_leaf_metadata(path)
    out: dict = {}
    with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ckptr:
        for kp, meta in flat:
            keys = [
                str(getattr(k, "key", getattr(k, "idx", k))) for k in kp
            ]
            leaf = restore_leaf(path, kp, meta, checkpointer=ckptr)
            node = out
            for k in keys[:-1]:
                node = node.setdefault(k, {})
            if (
                len(keys) >= 2
                and keys[-1] == "kernel"
                and keys[-2] in _QUANTIZED_KERNELS
            ):
                qs = _quantize_kernel(
                    keys[-2], leaf, quantize_int8,
                    # scan_layers checkpoints stack kernels under
                    # "layers/" with a leading layer axis — quantize per
                    # layer, never across the layer dim
                    stacked="layers" in keys[:-1],
                )
                del leaf  # free the f32 kernel before the next read
                node.update(
                    {
                        k: place(keys[:-1] + [k], v)
                        for k, v in qs.items()
                    }
                )
            else:
                node[keys[-1]] = place(keys, leaf)
    if not materialize:
        return out
    # without a mesh, restore_leaf lands leaves as host numpy, and jit
    # re-uploads numpy args on EVERY call (cost on the chip: not
    # measured); one on-device identity pass pins the tree on device.
    # See utils.tree.device_materialize.
    from pytorch_distributed_training_tutorials_tpu.utils.tree import (
        device_materialize,
    )

    return device_materialize(out)

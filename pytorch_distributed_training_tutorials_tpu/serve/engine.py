"""Continuous-batching serving engine: one compiled decode program,
``n_slots`` concurrent requests, launch-amortized chains.

The reference's serving story stops at loading Llama-7B for placement
(``/root/reference/03.model_parallel.ipynb`` cell 2 — never generates a
token; SURVEY.md section 5.7), and this repo's own ``generate()`` is
one-shot batch inference: every request in the batch waits for the whole
batch, and nobody new can join until the loop drains. This module is the
Orca-style (OSDI '22) fix, built TPU-native:

- ONE jitted decode program over a fixed ``(n_slots, ...)`` slot-indexed
  KV cache (:mod:`.slots`); requests at different depths decode together,
  each slot carrying its own position counter and active mask
  (``remaining > 0``);
- decode runs in CHAINS of ``tokens_per_launch`` steps per dispatch
  (``lax.scan``, one launch + ONE batched ``jax.device_get`` for the
  whole chain) because a launch and its fetch cost the host a fixed
  round trip regardless of how much work the launch carries (its size
  on the chip: not measured) — a per-token host sync would pay it once
  per token;
- finished slots are refilled in place by a jitted prefill-into-slot
  (bucketed prompt lengths, :func:`.slots.bucket_len`; splice + position
  reset, :func:`.slots.write_slot`) — no recompile per request, per
  prompt length (beyond the bucket set), or per slot;
- with ``prefix_cache_bytes > 0``, refill first consults a host-side
  radix index (:class:`.prefix.PrefixIndex`, the vLLM SOSP '23
  shared-prefix idea rebuilt for fixed shapes): a longest-prefix-match
  seeds the slot from a RETAINED device cache segment
  (:func:`.slots.seed_cache` + the same ``write_slot`` surgery) and
  prefills only the uncached suffix through the decode path's chunked
  continuation (``models/transformer.py`` ``_store_decode_kv``) — a
  deep hit turns an O(prompt) prefill into an O(suffix) one. Segment
  and suffix lengths reuse the pow2 bucket set, so the prefix cache
  adds a bounded set of compiles, and greedy token-exactness is
  preserved BITWISE for full-precision caches
  (tests/test_transformer.py pins the chunk-vs-prefill equality,
  tests/test_serve.py the end-to-end cache-on-vs-off stream);
- sampling is the SAME pipeline ``generate()`` uses
  (:mod:`..models.sampling`), vmapped over per-slot PRNG streams: a
  request's draws depend only on its own ``seed`` and draw index, never
  on co-scheduling;
- with ``speculative_k > 0``, every chain iteration is self-speculative
  (Leviathan et al. 2023 verify + Saxena 2023 prompt-lookup draft, no
  second model): ``k`` draft tokens per slot come from an on-device
  n-gram match over the slot's recent-token history (carried in the
  decode state — no host round-trip), ONE ``(n_slots, k+1)`` decode
  forward verifies them through the same chunked-continuation path the
  prefix cache relies on, and the longest accepted prefix lands while
  rejected positions are rewound (``rewind_cache_index``; the stale K/V
  rows are provably overwritten before any query can attend to them —
  see models/transformer.py). ``k`` is STATIC; the accepted length is
  *data*, so nothing recompiles and the chain still costs one launch +
  ONE batched fetch — it just returns an ``(n_slots, steps, k+1)``
  token block plus per-step emit counts instead of one token per step;
- with ``adapter_bank=...``, every slot carries a per-request LoRA
  adapter id (:mod:`..adapters`): the bank's stacked factors ride in the
  params tree, each slot's id is DATA gathered by
  :func:`..adapters.bank.apply_lora` inside the same compiled programs,
  so tenants with different adapters co-batch with zero recompiles and
  id 0 (zero factors) is EXACTLY the base model. ``Request.adapter`` is
  validated at :meth:`submit` (admission, like the window check), which
  also snapshots the row's tenant-generation — bank rows recycle, so a
  request whose tenant is evicted (or whose row is re-registered) while
  it queues completes with ``finish_reason == "adapter_evicted"``
  instead of decoding under the wrong factors. Prefix keys are
  namespaced per (adapter, generation) so tenants never splice each
  other's KV — not even a later tenant reusing an evicted tenant's row.
  ``register``/``evict`` on a live engine take effect at the next
  :meth:`step` (the engine re-merges automatically when the bank's
  version moves). Bank off keeps the state tree and compiled programs
  byte-identical.

Failure handling (ISSUE 9) lives at the SAME boundaries the scheduler
does — between chains and at refill, never inside a compiled program:

- deadlines (``Request.deadline_s`` / engine ``default_deadline_s``)
  and host-side :meth:`cancel` complete a request ``"deadline"`` /
  ``"cancelled"`` at the next chain/refill boundary via the existing
  park path (partial tokens kept; a queued victim completes with zero
  device work, like ``"adapter_evicted"``);
- :meth:`close` stops admission (``QueueClosed`` backpressure) and
  :meth:`drain` runs every accepted request to completion — graceful
  shutdown without dropping in-flight work;
- with ``guard_nonfinite=True`` the chain also emits a per-slot
  finite-logits flag per step, riding the SAME batched fetch (budget
  unchanged): a request that drives logits to NaN/Inf completes
  ``"nonfinite"`` with its pre-poison tokens, its slot parks and is
  rewritten whole by the next refill (quarantine), and co-scheduled
  slots — independent across the batch dim — keep decoding
  token-identically to a clean run;
- a prefill that RAISES (hardware fault, injected chaos) is isolated to
  its request (``"error"``, slot parked, engine keeps serving);
- a :class:`..utils.chaos.ChaosConfig` injects deterministic faults
  (NaN logits at (slot, step), prefill failure, launch stall) so every
  path above is exercised by tests, not just reasoned about.

Guard/deadline/chaos OFF keeps the state tree and compiled programs
byte-identical to the pre-robustness engine (the same Python-default
trick the prefix cache, speculation, and adapter bank use).

Pipelining (ISSUE 11) hides the per-LAUNCH host roundtrip (its size on
the chip: not measured) behind device execution:

- ``pipeline_depth=2`` double-buffers decode chains: chain ``i+1`` (and
  any prefill/splice for slots freed at chain ``i-1``'s observed
  boundary) is DISPATCHED before chain ``i``'s batched fetch — JAX
  async dispatch queues it device-side, so the device never idles on
  the roundtrip. Host bookkeeping (sweep, distribute, refill) runs one
  chain behind the device: "chain boundary" for deadlines / cancel /
  quarantine means the OBSERVED boundary (one chain late at depth 2;
  tokens earned before it are kept, exactly as before). Token-exactness
  is unaffected because chain ``i+1``'s inputs are device-resident
  state, never chain ``i``'s fetched tokens; a slot whose request
  finished in chain ``i`` junk-decodes one extra chain (its rows are
  dropped by an identity check against the slot view snapshotted at
  dispatch) and parks/refills as usual. Depth 1 IS the serial loop —
  byte-identical state tree and compiled programs;
- ``prefill_chunk=N`` caps prefill work per scheduling quantum: a
  prompt whose uncached length exceeds N prefills in N-token chunks
  through the SAME bitwise-equal chunked decode continuation splices
  use, one chunk per :meth:`ServeEngine.step`, interleaved with decode
  chains — a 2048-token prompt no longer freezes co-scheduled slots.
  Chunks accumulate in a batch-1 side cache (never the slot state); the
  final chunk splices into the slot exactly like a prefix-cache hit and
  only THAT chunk fetches the first token, so the fetch budget stays
  chains + prefills + splices in every configuration.

Greedy decoding is token-exact vs one-shot ``generate()`` (same math,
same cache semantics; pinned by tests/test_serve.py). Temperature /
top-k / top-p are ENGINE-level statics — per-request sampling params
would either recompile the decode program or drag filter branches into
every step; per-request randomness comes from per-request seeds.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.numpy as jnp

from pytorch_distributed_training_tutorials_tpu.models.sampling import (
    ngram_draft,
    sample_logits,
    sample_logits_per_slot,
    speculative_accept,
)
from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    _kv_quant_mode,
    park_cache_index,
    rewind_cache_index,
)
from pytorch_distributed_training_tutorials_tpu.parallel.tensor_parallel import (
    audit_hlo,
)
from pytorch_distributed_training_tutorials_tpu.serve.pages import (
    PagePool,
    PoolExhausted,
)
from pytorch_distributed_training_tutorials_tpu.serve.prefix import PrefixIndex
from pytorch_distributed_training_tutorials_tpu.serve.scheduler import (
    Completion,
    FifoScheduler,
    Handoff,
    Request,
)
from pytorch_distributed_training_tutorials_tpu.serve.slo import (
    PriorityScheduler,
    SwapRecord,
    choose_victim,
)
from pytorch_distributed_training_tutorials_tpu.serve.slots import (
    _POOL_TO_FLAT,
    _leaf_name,
    bucket_len,
    extract_segment,
    init_slot_state,
    slot_bytes,
    seed_cache,
    tree_nbytes,
    tree_nbytes_sharded,
    write_slot,
    write_slot_paged,
    zero_cache,
)
from pytorch_distributed_training_tutorials_tpu.utils import chaos as chaos_lib
from pytorch_distributed_training_tutorials_tpu.utils.profiling import annotate


class _Active:
    """Host-side view of one occupied slot. ``segment`` pins the prefix
    segment this slot was spliced from (released at completion);
    ``ttft_s`` is submit-to-first-token wall time."""

    __slots__ = ("request", "tokens", "remaining", "segment", "ttft_s",
                 "pages")

    def __init__(self, request: Request, first_token: int):
        self.request = request
        self.tokens = [first_token]
        self.remaining = request.max_new_tokens - 1
        self.segment = None
        self.ttft_s = 0.0
        # paged engines (ISSUE 13): pool page ids this slot holds one
        # reference to each — released when the slot parks
        self.pages: list[int] = []


class _InFlight:
    """One dispatched-but-not-yet-fetched decode chain: the chain's
    output futures, a shallow snapshot of the slot views at dispatch
    (the identity guard — a slot completed or refilled inside the
    pipeline window must not consume this chain's junk rows), and the
    chain's sequence number for the flight recorder's overlap stamp."""

    __slots__ = ("out", "view", "chain_id")

    def __init__(self, out, view, chain_id: int):
        self.out = out
        self.view = view
        self.chain_id = chain_id


class _PendingPrefill:
    """Host-side record of a chunked prefill in progress: the request,
    its target slot, the accumulating batch-1 side cache (device
    futures — chunks are async dispatches, never fetched), and how many
    prompt tokens (``done``, INCLUDING any spliced prefix ``depth``)
    the cache already holds. The slot's device-side budget stays 0
    until the final chunk, so decode chains treat it as inactive."""

    __slots__ = ("request", "slot", "cache1", "prompt", "aid", "done",
                 "depth", "segment", "grow", "pkey", "pages")

    def __init__(self, request: Request, slot: int):
        self.request = request
        self.slot = slot
        self.cache1 = None
        self.prompt: list[int] = []
        self.aid = 0
        self.done = 0
        self.depth = 0
        self.segment = None
        self.grow = False
        self.pkey: list[int] = []
        # paged engines (ISSUE 13): pages pre-allocated for the slot at
        # chunking start (all fresh — chunked prompts don't share)
        self.pages: list[int] = []


class ServeEngine:
    """Request-level LM serving over a slot-indexed KV cache.

    ``model`` is a :class:`..models.transformer.TransformerLM` (or
    anything with the same decode/prefill/``last_pos`` apply contract and
    a ``cfg.max_seq_len``); its ``max_seq_len`` is the serving window
    every slot gets. ``params`` stays caller-owned and read-only (share
    one tree across engines; int8/TP placements pass straight through —
    the engine never touches leaf placement).

    Drive it with :meth:`submit` + :meth:`step`, or :meth:`run_until_idle`
    to drain everything. ``step()`` does at most: one prefill launch per
    freed slot (each with one scalar fetch of the first sampled token),
    then ONE ``tokens_per_launch``-step decode chain with ONE batched
    fetch — the no-per-token-host-sync contract tests/test_serve.py pins
    with a monkeypatched ``jax.device_get``.
    """

    def __init__(
        self,
        model,
        params,
        *,
        n_slots: int = 4,
        tokens_per_launch: int = 8,
        max_queue: int = 64,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        prefix_cache_bytes: int = 0,
        min_hit_depth: int = 1,
        speculative_k: int = 0,
        spec_ngram: int = 3,
        adapter_bank=None,
        default_deadline_s: float | None = None,
        guard_nonfinite: bool = False,
        chaos=None,
        flight=None,
        sentry=None,
        pipeline_depth: int = 1,
        prefill_chunk: int = 0,
        paged: bool = False,
        page_size: int = 0,
        pool_pages: int = 0,
        strategy=None,
        kv_bits: int | None = None,
        paged_kernel: bool = False,
        role: str | None = None,
        priority_classes: int = 0,
    ):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if tokens_per_launch < 1:
            raise ValueError("tokens_per_launch must be >= 1")
        # paged KV (ISSUE 13): off = byte-identical state tree + compiled
        # programs to the whole-slot engine (the geometry kwargs must not
        # be set, so an off engine can never half-configure a pool)
        if paged:
            if page_size < 1 or pool_pages < 1:
                raise ValueError(
                    "paged=True needs page_size >= 1 and pool_pages >= 1"
                )
        elif page_size or pool_pages:
            raise ValueError(
                "page_size/pool_pages require paged=True"
            )
        # quantized KV + fused kernel (ISSUE 17): both ENGINE-static —
        # kv_bits rebuilds the model config (a different cache storage
        # dtype is a different compiled program family) and paged_kernel
        # flips the decode read path between the jnp.take reference and
        # the Pallas page-walk kernel. Per-request values for either
        # would recompile; neither exists.
        if kv_bits not in (None, 4, 8):
            raise ValueError(
                "kv_bits must be None (follow the model config), 8 "
                "(int8 + f32 scales), or 4 (packed nibbles + bf16 "
                "scales)"
            )
        if paged_kernel and not paged:
            raise ValueError(
                "paged_kernel=True requires paged=True (the kernel "
                "walks the page pool; whole-slot decode has no pages)"
            )
        if speculative_k < 0:
            raise ValueError("speculative_k must be >= 0")
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1 (1 = serial)")
        # disaggregation (ISSUE 18): role=None is the monolithic engine
        # (byte-identical state tree + compiled programs — no handoff
        # twins are even constructed). A prefill-role engine runs
        # admission + prefill only and EMITS segments; a decode-role
        # engine ACCEPTS them and decodes. Features that only make
        # sense on the other side are rejected at construction so a
        # half-configured role can never exist: prefill never decodes
        # (no paged pool, no speculation, no chains to pipeline) and
        # decode never prefills a prompt (prefix cache + chunked
        # prefill live where the prefill forward runs).
        if role not in (None, "prefill", "decode"):
            raise ValueError(
                f"role must be None (monolithic), 'prefill', or "
                f"'decode'; got {role!r}"
            )
        self._role = role
        if role == "prefill":
            if paged:
                raise ValueError(
                    "role='prefill' engines never decode — the paged "
                    "pool belongs on the decode side"
                )
            if speculative_k:
                raise ValueError(
                    "role='prefill' engines never decode — speculation "
                    "belongs on the decode side"
                )
            if pipeline_depth != 1:
                raise ValueError(
                    "role='prefill' engines dispatch no decode chains — "
                    "pipeline_depth belongs on the decode side"
                )
        if role == "decode":
            if prefix_cache_bytes:
                raise ValueError(
                    "role='decode' engines never prefill a prompt — the "
                    "prefix cache belongs on the prefill side"
                )
            if prefill_chunk:
                raise ValueError(
                    "role='decode' engines never prefill a prompt — "
                    "prefill_chunk belongs on the prefill side"
                )
        if prefill_chunk and (
            prefill_chunk < 8 or prefill_chunk & (prefill_chunk - 1)
        ):
            raise ValueError(
                "prefill_chunk must be 0 (off) or a power of two >= 8 "
                "(chunk lengths must come from the pow2 bucket set so "
                "compiles stay bounded)"
            )
        if default_deadline_s is not None and default_deadline_s <= 0:
            raise ValueError(
                "default_deadline_s must be > 0 (None = no deadline)"
            )
        # SLO tiers (ISSUE 20): 0 = off — the engine keeps the FIFO
        # scheduler and constructs NO swap programs, so off engines are
        # byte-identical (state tree + compiled-program census) to the
        # pre-SLO build. N >= 1 admits priority classes [0, N), pops by
        # (class, arrival), and under pressure preempts the lowest-tier
        # active slot at the chain boundary via the KV swap path below.
        if priority_classes < 0:
            raise ValueError(
                "priority_classes must be >= 0 (0 = single-class FIFO)"
            )
        if priority_classes and role is not None:
            raise ValueError(
                "priority_classes requires role=None: preemption swaps "
                "in through the monolithic refill path; role-split "
                "fleets shape traffic at the router"
            )
        self._slo = priority_classes > 0
        self._n_classes = int(priority_classes)
        # sharded serving (ISSUE 15): a TensorParallel strategy shards the
        # slot/KV state on the model (head) axis to match the attention
        # sharding the params already carry — TP serving is the existing
        # engine under jit on a mesh, not a second engine. tp=1 (or
        # strategy=None) is byte-identical to the replicated engine: the
        # gate below makes every _pin() a Python-level identity, so no
        # jaxpr, state leaf, or compile count changes off-path.
        self._strategy = strategy
        self._shard = (
            strategy is not None and getattr(strategy, "tp_size", 1) > 1
        )
        self._tp = strategy.tp_size if self._shard else 1
        self._tp_audit = None
        # per-chip byte accounting: a sharded leaf's honest HBM claim is
        # its SHARD size, not its global size (page pricing + prefix
        # index budgets below go through this)
        self._nbytes = tree_nbytes_sharded if self._shard else tree_nbytes
        # adapter bank: None = off (the engine then builds byte-identical
        # state and compiled programs to the adapter-free one). On, the
        # engine serves the bank's LoRA twin of the caller's model over
        # merged params (base tree + stacked factor subtrees); the base
        # tree stays caller-owned and untouched.
        self._bank = adapter_bank
        self._adapters = adapter_bank is not None
        if self._adapters:
            base_cfg = dataclasses.replace(
                model.cfg, lora_adapters=0, lora_rank=0
            )
            bank_base = dataclasses.replace(
                adapter_bank.model.cfg, lora_adapters=0, lora_rank=0
            )
            if base_cfg != bank_base:
                raise ValueError(
                    "adapter_bank was built for a different model config"
                )
            self._base_params = params
            model = adapter_bank.model
            params = adapter_bank.merge_params(params)
            # bank version this merge reflects; step() re-merges when
            # the bank moves past it (register/evict on a live engine)
            self._merged_version = adapter_bank.version
        if self._shard:
            # commit params to their rule shardings (idempotent for
            # already-placed trees): committed sharded inputs are what
            # make every jit below compile GSPMD-sharded programs
            # instead of replicated ones
            params = strategy.shard_state(params)
        # kv_bits (ISSUE 17): override the cache storage dtype on the
        # model the engine serves (bank twin included — the override
        # runs AFTER the bank substitution so tenants quantize too).
        # Params are untouched: kv_cache_dtype only shapes the mutable
        # cache collection, so None keeps engine + model byte-identical
        # to a no-kwarg construction. 8 -> int8 + f32 scales; 4 ->
        # packed-nibble uint8 + bf16 scales, EXACTLY half int8's bytes
        # per token-head (d/2 + 2 vs d + 4 — models/transformer.py
        # _kv_storage), which is what makes "2x pages at fixed HBM" an
        # identity rather than an approximation.
        if kv_bits is not None:
            model = type(model)(
                cfg=dataclasses.replace(
                    model.cfg,
                    kv_cache_dtype="int4" if kv_bits == 4 else jnp.int8,
                )
            )
        if self._shard and model.cfg.tp_mesh is None:
            # the model sees the mesh it is served under: its decode step
            # keeps the plain head-sharded einsums (a bare pallas_call is
            # refused on the stack GSPMD has sharded)
            model = type(model)(
                cfg=dataclasses.replace(model.cfg, tp_mesh=strategy.mesh)
            )
        if getattr(model.cfg, "latent", False):
            # a latent cache (one [c | k_rope] row a token, shared by all
            # heads) is known to the whole-slot engine alone so far
            _whole_slots_only(
                "a model with latent attention (kv_lora_rank > 0)", (
                    ("paged=True (the page pool holds heads of K and V)",
                     paged),
                    ("prefix_cache_bytes (the prefix cache's segments)",
                     prefix_cache_bytes > 0),
                    ("a tensor-parallel strategy (the slot rules shard "
                     "K and V by head)", self._shard),
                    ("speculative_k", speculative_k > 0),
                    ("kv_bits (a quantized cache)", kv_bits is not None),
                    ("an adapter bank", self._adapters),
                ))
        if getattr(model.cfg, "recurrent", False):
            # recurrent state beside K and V (models/sambay.py: rings and
            # one shared cache; models/mamba2.py: a state and a whole cache
            # in every layer): a slot holds them whole, and nothing that
            # cuts a sequence's cache by position, rewinds it or moves it
            # knows yet what a state or a ring is
            _whole_slots_only(
                "a model with recurrent state (mb_per_layer or "
                "mamba_n_heads > 0)", (
                    ("paged=True (a page holds heads of K and V at "
                     "absolute positions: neither a state nor a ring)",
                     paged),
                    ("prefix_cache_bytes (a prefix segment is rows of K "
                     "and V: it holds no state at its end)",
                     prefix_cache_bytes > 0),
                    ("prefill_chunk (a chunk continues a cache by "
                     "position: the state would have to continue too)",
                     prefill_chunk > 0),
                    ("speculative_k (a rejected draft rewinds a depth; a "
                     "state cannot be rewound)", speculative_k > 0),
                    ("kv_bits (a quantized cache)", kv_bits is not None),
                    ("a tensor-parallel strategy (the slot rules shard K "
                     "and V by head and know no state leaf)", self._shard),
                    ("an adapter bank", self._adapters),
                    ("priority_classes (preemption swaps a slot's K and V "
                     "out by position)", priority_classes > 0),
                    ("role (a handoff ships a segment of K and V by "
                     "position)", role is not None),
                ))
        self._kv_bits = {None: 0, "int8": 8, "int4": 4}[
            _kv_quant_mode(model.cfg.kv_cache_dtype)
        ]
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.tokens_per_launch = tokens_per_launch
        self.window = int(model.cfg.max_seq_len)
        # paged KV decode (ISSUE 13): the DECODE-side model reads/writes
        # K/V through a shared page pool + per-slot page tables
        # (cfg.kv_pages/kv_page_size — models/transformer.py), so slot
        # count decouples from window size: n_slots * window may exceed
        # pool_pages * page_size, with admission backpressure
        # (PoolExhausted) when a request can never fit. Prefill/chunk
        # programs keep the UNPAGED batch-1 layout (self.model) and the
        # scatter into the pool happens in write_slot_paged. When off,
        # _dec_model IS self.model, so every chain jaxpr below is
        # byte-identical to the whole-slot engine's.
        self._paged = bool(paged)
        self._page_size = int(page_size)
        self._pool_pages = int(pool_pages)
        if self._paged:
            if self.window % self._page_size:
                raise ValueError(
                    f"page_size ({page_size}) must divide the window "
                    f"({self.window}) so slot page tables have one "
                    "fixed length"
                )
            self._pool = PagePool(pool_pages, page_size)
            self._pages_per_slot = self.window // self._page_size
            # paged_kernel rides the decode model's config: the flag is
            # trace-time structure (models/transformer.py branches on it
            # in Python, never on a traced value), so kernel-off paged
            # engines compile byte-identical programs to pre-kernel ones.
            self._dec_model = type(model)(
                cfg=dataclasses.replace(
                    model.cfg, kv_pages=pool_pages,
                    kv_page_size=page_size,
                    paged_kernel=bool(paged_kernel),
                )
            )
        else:
            self._pool = None
            self._pages_per_slot = 0
            self._dec_model = model
        self._paged_kernel = bool(paged_kernel)
        # speculate-k: 0 = off (the engine then compiles byte-identical
        # programs to the pre-speculation one — no hist state, old chain)
        self._spec = speculative_k > 0
        self._spec_k = int(speculative_k)
        self._spec_ngram = int(spec_ngram)
        if self._spec and speculative_k + 1 > self.window:
            raise ValueError("speculative_k + 1 must fit the window")
        if self._spec and spec_ngram < 1:
            raise ValueError("spec_ngram must be >= 1")
        self.scheduler = (
            PriorityScheduler(
                self.window, max_queue=max_queue,
                n_classes=self._n_classes,
            )
            if self._slo
            else FifoScheduler(self.window, max_queue=max_queue)
        )
        self._slots: list[_Active | None] = [None] * n_slots
        self._state = init_slot_state(
            self._dec_model, params, n_slots,
            history=self.window if self._spec else 0,
            adapters=self._adapters,
            paged=self._pool_pages if self._paged else 0,
            strategy=strategy if self._shard else None,
        )
        self._scan_layers = bool(getattr(model.cfg, "scan_layers", False))
        if self._paged:
            # per-page HBM footprint (all pool leaves / pool_pages) —
            # page_stats()'s hbm_high_water_bytes and the prefix index's
            # byte accounting both price pages with it. Host metadata
            # only; tree_nbytes never touches the device.
            pool_leaves = [
                leaf for path, leaf in
                jax.tree_util.tree_leaves_with_path(self._state["cache"])
                if _leaf_name(path) in _POOL_TO_FLAT
            ]
            self._page_bytes = self._nbytes(pool_leaves) // self._pool_pages
        else:
            self._page_bytes = 0
        self._temperature = float(temperature)
        self._top_k = int(top_k)
        self._top_p = float(top_p)
        # prefix cache: 0 bytes = off (the engine is then byte-identical
        # in behavior to the pre-prefix-cache one)
        self._retain = prefix_cache_bytes > 0
        # paged engines hand the index an eviction hook so a segment's
        # page refcounts flow back to the pool the moment the index
        # drops it (the index stays jax-free and handle-agnostic: a
        # paged handle is a tuple of page ids, not a device tree)
        self.prefix = (
            PrefixIndex(
                prefix_cache_bytes,
                on_evict=self._release_segment_pages if self._paged
                else None,
            )
            if self._retain else None
        )
        self._min_hit_depth = int(min_hit_depth)
        # software pipeline (ISSUE 11): depth 1 = today's serial loop
        # (dispatch then fetch in the same step — byte-identical state
        # tree and compiled programs); depth 2 keeps one chain in flight
        # across the host roundtrip. prefill_chunk = 0 disables chunked
        # prefill (every prompt prefills whole, as before).
        self._depth = int(pipeline_depth)
        self._chunk = int(prefill_chunk)
        self._inflight: collections.deque[_InFlight] = collections.deque()
        self._pending: dict[int, _PendingPrefill] = {}
        # the rows of a ring of K and V (a model whose window layers keep
        # one: models/sambay.py), as its leaf in the cache lies (..., ring,
        # D); 0: no ring. What _chain_rows counts a ring's reads by
        self._ring = next((
            leaf.shape[-2] for path, leaf in
            jax.tree_util.tree_leaves_with_path(self._state["cache"])
            if _leaf_name(path) == "window_key"
        ), 0)
        self.n_chunks = 0
        if self._retain or self._chunk or role == "decode" or self._slo:
            # shape/dtype proto of the batch-1 decode cache — seed_cache
            # builds the splice start state from it, chunked prefill its
            # zeroed side cache, a decode-role engine both validates
            # incoming handoff segments against it and seeds their
            # accept splice from it, and the SLO swap-in re-splices a
            # preempted request's parked segment through it
            # (eval_shape: no FLOPs, no buffers)
            self._proto1 = jax.eval_shape(
                lambda p, t: self.model.apply(
                    {"params": p}, t, decode=True, mutable=["cache"]
                )[1]["cache"],
                params, jnp.zeros((1, 1), jnp.int32),
            )
        # stats for receipts
        self.n_prefills = 0
        self.n_chains = 0
        self.n_splices = 0
        self.prefix_hit_tokens = 0
        self.generated_tokens = 0
        # speculative counters: sequential verify forwards dispatched,
        # verify steps whose tokens an active slot consumed, and draft
        # tokens accepted (emitted beyond the guaranteed 1/step)
        self.n_verify_forwards = 0
        self.spec_steps_consumed = 0
        self.spec_drafts_accepted = 0
        # requests served with a non-base adapter, and requests bounced
        # at refill because their tenant was evicted / their row
        # re-registered while queued (receipt counters)
        self.adapter_requests = 0
        self.adapter_rejected = 0
        # robustness layer (ISSUE 9): deadlines/cancel/drain are pure
        # host bookkeeping (no compiled-program impact at all); the
        # non-finite guard changes only the chain's OUTPUT (the flag
        # rides the existing batched fetch), never the state tree.
        self._deadline = default_deadline_s
        self._guard = bool(guard_nonfinite)
        self._chaos = chaos
        # flight recorder (ISSUE 10): None = off. On, lifecycle events
        # and spans are stamped at the SAME host boundaries the code
        # below already touches — a clock read + a deque append, never a
        # device fetch, so the fetch budget and the compiled programs are
        # IDENTICAL either way (tests/test_serve.py pins both).
        self._flight = flight
        # contract sentry (ISSUE 19): None = off (byte-identical state
        # tree + compiled programs — the sentry only ever counts on the
        # host). On, every step() round runs inside a begin/end fetch
        # accounting window, the budgeted call sites attribute their
        # fetches through _sentry_fetch, and the chain's dispatch args
        # are walked for host-numpy re-upload leaves.
        self._sentry = sentry
        self._inject_logits = chaos is not None and chaos.poisons_logits
        self._cancelled: set[int] = set()
        self.n_deadline_expired = 0
        self.n_cancelled = 0
        self.nonfinite_quarantined = 0
        self.n_prefill_errors = 0
        # disaggregation (ISSUE 18): transfer records waiting for the
        # router to collect (prefill role, keyed by request id) / to be
        # spliced at refill (decode role); host dicts holding device
        # futures — never fetched here
        self._handoffs: dict[int, Handoff] = {}
        self._handoff_in: dict[int, Handoff] = {}
        self.n_handoffs_out = 0
        self.n_handoffs_in = 0
        # SLO preemption (ISSUE 20): parked swap records by request id
        # (host numpy — the swap-out fetch already paid for the bytes),
        # the one-shot latch for the chaos force-preempt injector, and
        # the receipt counters. Attrs exist only when the feature is on
        # (the attrs-don't-exist off-path contract).
        if self._slo:
            self._swapped: dict[int, SwapRecord] = {}
            self._chaos_preempt_fired = False
            self.n_swaps_out = 0
            self.n_swaps_in = 0
        # donating the state tree lets XLA update the multi-hundred-MB
        # cache in place; CPU jit warns on donation (unsupported), so
        # only donate where it is real
        donate = (1,) if jax.default_backend() in ("tpu", "gpu") else ()
        # classic and paged prefill/splice programs are MUTUALLY
        # EXCLUSIVE per engine: an unpaged engine never constructs the
        # paged twins (so its compiled-program census is byte-identical
        # to the pre-paging engine), and a paged engine never constructs
        # the whole-slot ones.
        if self._paged:
            self._prefill_paged = jax.jit(
                self._prefill_paged_fn, donate_argnums=donate
            )
        else:
            self._prefill = jax.jit(
                self._prefill_fn, donate_argnums=donate
            )
        # logit-poison chaos threads a traced chain-base scalar into the
        # chain (an EXTRA operand) — a separate wrapper keeps the
        # chaos-free jaxpr byte-identical to the pre-robustness one
        if self._spec:
            chain_fn = (
                self._spec_chain_chaos_fn if self._inject_logits
                else self._spec_chain_fn
            )
        else:
            chain_fn = (
                self._chain_chaos_fn if self._inject_logits
                else self._chain_fn
            )
        self._chain = jax.jit(chain_fn, donate_argnums=donate)
        # splice: same donation as prefill (state is arg 1); the retained
        # segment (arg 2) must NEVER be donated — the index keeps serving
        # it to later requests. The two compile statics are keyword-only,
        # by NAME: for a jitted BOUND method argnums exclude self (unlike
        # the nn.remat(Block, static_argnums=...) idiom which counts it),
        # and names are unambiguous under both conventions.
        if self._paged:
            # paged splice: no static argnames — shared/boundary page
            # geometry rides as traced data (the row vector + the CoW
            # src/dst pair, sentinel = no-op), so compiles stay one per
            # suffix bucket. The parked-table program sentinels a slot's
            # page-table row so chains dispatched after a completion
            # never write through freed page ids.
            self._splice_paged = jax.jit(
                self._splice_paged_fn, donate_argnums=donate
            )
            self._paged_park = jax.jit(
                self._paged_park_fn, donate_argnums=(0,) if donate else ()
            )
        else:
            self._splice = jax.jit(
                self._splice_fn, static_argnames=("seg_len", "grow"),
                donate_argnums=donate,
            )
        self._park = jax.jit(
            _park_slot, donate_argnums=(0,) if donate else ()
        )
        # chunked-prefill programs exist only when the feature is on —
        # chunk-off engines compile (and trace) nothing new. The seeded
        # segment is never donated (the index keeps serving it); the
        # side cache IS donated between chunks (it has exactly one
        # consumer), as is the slot state into the final splice.
        if self._chunk:
            self._chunk_zero = jax.jit(
                lambda: self._pin(zero_cache(self._proto1))
            )
            self._chunk_step = jax.jit(
                self._chunk_step_fn, donate_argnums=donate
            )
            if self._paged:
                # paged seed: gather-COPY the donor's pages out of the
                # live pool into the unpaged batch-1 side cache. Reads
                # live state, so NEVER donated. The paged final chunk
                # scatters the side cache into the slot's fresh pages
                # (write_slot_paged) — side cache + slot state donated
                # as in the classic twin.
                self._chunk_seed_paged = jax.jit(
                    self._chunk_seed_paged_fn
                )
                self._chunk_final_paged = jax.jit(
                    self._chunk_final_paged_fn,
                    donate_argnums=(1, 2) if donate else (),
                )
            else:
                self._chunk_seed = jax.jit(
                    lambda segment, depth: self._pin(seed_cache(
                        self._proto1, segment, depth
                    ))
                )
                self._chunk_final = jax.jit(
                    self._chunk_final_fn,
                    static_argnames=("seg_len", "grow"),
                    donate_argnums=(1, 2) if donate else (),
                )
        # disaggregation programs (ISSUE 18): role=None constructs
        # NEITHER side, so monolithic engines keep a byte-identical
        # compiled-program census. The prefill role's programs end in
        # segment extraction instead of slot surgery; the decode role's
        # accept is the prefix-splice surgery (seed_cache + write_slot)
        # applied to a TRANSFERRED segment. The segment is never
        # donated on either side — the prefill engine's prefix index
        # (and the router, across replica death) may still serve it.
        if role == "prefill":
            self._handoff_prefill = jax.jit(self._handoff_prefill_fn)
            if self._retain:
                self._handoff_splice = jax.jit(
                    self._handoff_splice_fn,
                    static_argnames=("seg_len",),
                )
            if self._chunk:
                # the accumulated side cache has exactly one consumer
                self._handoff_final = jax.jit(
                    self._handoff_final_fn,
                    static_argnames=("seg_len",),
                    donate_argnums=donate,
                )
        elif role == "decode":
            self._accept_jit = jax.jit(
                self._accept_paged_fn if self._paged
                else self._accept_fn,
                donate_argnums=donate,
            )
        # SLO swap programs (ISSUE 20): constructed only under
        # priority_classes, so FIFO engines keep a byte-identical
        # compiled-program census. Swap-out reads live state (the slot
        # may keep decoding if the preemption re-check bails) — never
        # donated; its seg_len is STATIC from the same pow2 bucket
        # family as prefill, so swaps never mint per-length compiles.
        # Swap-in is the accept splice pointed at a host-parked segment:
        # slot state donated like every other refill-time surgery.
        if self._slo:
            self._swap_out_jit = jax.jit(
                self._swap_out_paged_fn if self._paged
                else self._swap_out_fn,
                static_argnames=("seg_len",),
            )
            self._swap_in_jit = jax.jit(
                self._swap_in_paged_fn if self._paged
                else self._swap_in_fn,
                donate_argnums=donate,
            )

    # ------------------------------------------------------------------
    # compiled programs (closures over model + static sampling params)
    # ------------------------------------------------------------------

    def _pin(self, tree):
        """Pin ``tree``'s cache leaves to the strategy's slot shardings.

        Sharded engines thread this through every compiled cache
        producer (prefill write, splice seed, chunk accumulate, chain
        carry) so GSPMD keeps K/V head-sharded END TO END — without the
        constraint, a DUS or gather whose index operands are replicated
        can tempt the partitioner into an all-gather + local-update +
        reshard round trip. Off-path (``strategy=None`` or tp=1) this is
        a Python-level identity: no constraint op enters the jaxpr, so
        the unsharded engine's compiled programs stay byte-identical
        (the same off-path trick as guard/chaos/spec/adapters). Specs
        resolve from the traced leaf shapes, so the ONE helper covers
        slot caches, batch-1 segments, and side caches alike."""
        if not self._shard:
            return tree
        return self._strategy.constrain_slot_tree(tree)

    def _prefill_fn(self, params, state, tokens, p_len, slot, seed,
                    max_new, aid=0):
        """Prefill ``tokens`` (1, bucket) into slot ``slot``: one batched
        forward populates the slot's K/V for ``[0, p_len)``, the first
        token is sampled from the logits gathered at the last REAL prompt
        position, and the slot's counters reset. All of ``p_len`` /
        ``slot`` / ``seed`` / ``max_new`` are traced scalars — one
        compile per prompt BUCKET, not per request.

        ``aid`` (the request's adapter id) is only PASSED when the bank
        is on — adapters off leave it the Python default 0, a jit-inert
        constant, so the adapter-free jaxpr is byte-identical to the
        pre-adapter engine's. On, it is a traced scalar threaded into the
        forward as ``adapter_ids`` and recorded in the slot state for the
        chain's per-slot gather.

        With the prefix cache on, the bucket-length leading chunk of the
        just-prefilled batch-1 cache rides out as a retained segment
        (:func:`.slots.extract_segment` — insert-on-prefill); ``()``
        otherwise, so the cache-off engine's compiled program is
        unchanged."""
        kw = {}
        if self._adapters:
            kw["adapter_ids"] = jnp.asarray(aid, jnp.int32)
        logits, upd = self.model.apply(
            {"params": params}, tokens, prefill=True, mutable=["cache"],
            last_pos=p_len - 1, **kw,
        )
        key = jax.random.PRNGKey(seed)
        first, key = sample_logits(
            logits[:, -1].astype(jnp.float32), key,
            self._temperature, self._top_k, self._top_p,
        )
        cache = self._pin(write_slot(
            state["cache"], upd["cache"], slot, p_len, self._scan_layers
        ))
        seg = (
            self._pin(extract_segment(
                upd["cache"], tokens.shape[1], self._scan_layers
            ))
            if self._retain
            else ()
        )
        new_state = {
            "cache": cache,
            "last_tok": state["last_tok"].at[slot].set(first[0]),
            "keys": state["keys"].at[slot].set(key),
            # the first generated token is already accounted for
            "remaining": state["remaining"].at[slot].set(max_new - 1),
        }
        if self._spec:
            new_state.update(_seed_history(
                state, tokens, p_len, slot, first[0]
            ))
        if self._adapters:
            new_state["adapter_ids"] = state["adapter_ids"].at[slot].set(
                jnp.asarray(aid, jnp.int32)
            )
        return new_state, first[0], seg

    def _splice_fn(self, params, state, segment, suffix, full, depth,
                   p_len, slot, seed, max_new, aid=0, *, seg_len, grow):
        """Prefix-cache-hit refill: seed a batch-1 cache from a retained
        ``segment`` at ``depth`` reused positions, run ONE chunked decode
        over the bucket-padded ``suffix`` (1, s_bucket) — the suffix
        prefill, same math as batched prefill (models/transformer.py
        decode S>1; bit-equal for full-precision caches,
        tests/test_transformer.py) — then splice the result into
        ``slot`` exactly like :meth:`_prefill_fn` does. The first token
        samples from the logits at the last REAL suffix token
        (``last_pos = p_len - 1 - depth``, local), so a hit is
        token-identical to a full prefill.

        ``seg_len`` / ``grow`` are STATIC: segment + suffix lengths come
        from the pow2 bucket set, so compiles stay bounded by (segment
        bucket, suffix bucket, grow) triples, never per request. With
        ``grow`` the full-prompt segment rides out for insertion —
        multi-turn streams deepen the index one splice at a time.

        ``full`` is the whole bucket-padded prompt (1, bucket) — the
        n-gram draft history must cover the REUSED prefix too, which
        ``suffix`` alone cannot seed. Speculation off passes the suffix
        array again; the operand is then dead and XLA drops it.

        ``aid`` follows the :meth:`_prefill_fn` contract (Python-default
        0 when adapters are off, traced scalar when on). Splices only
        ever reuse segments from the SAME adapter — ``_refill``
        namespaces prefix keys per adapter — so the seeded prefix K/V
        was computed under the same factors the suffix prefill applies."""
        kw = {}
        if self._adapters:
            kw["adapter_ids"] = jnp.asarray(aid, jnp.int32)
        cache1 = self._pin(seed_cache(self._proto1, segment, depth))
        return self._finish_prefill(
            params, cache1, state, suffix, p_len - 1 - depth, full,
            p_len, slot, seed, max_new, aid, kw, seg_len, grow,
        )

    def _finish_prefill(self, params, cache1, state, suffix, last_local,
                        full, p_len, slot, seed, max_new, aid, kw,
                        seg_len, grow):
        """Shared tail of :meth:`_splice_fn` and :meth:`_chunk_final_fn`:
        run the chunked decode continuation over ``suffix`` from the
        batch-1 ``cache1``, sample the first token from the logits at
        local position ``last_local``, and splice the result into
        ``slot``. A plain helper, not a jit target — it traces inline in
        its callers, so factoring it out changed neither jaxpr."""
        logits, upd = self.model.apply(
            {"params": params, "cache": cache1}, suffix, decode=True,
            mutable=["cache"], last_pos=last_local, **kw,
        )
        key = jax.random.PRNGKey(seed)
        first, key = sample_logits(
            logits[:, -1].astype(jnp.float32), key,
            self._temperature, self._top_k, self._top_p,
        )
        cache = self._pin(write_slot(
            state["cache"], upd["cache"], slot, p_len, self._scan_layers
        ))
        seg = (
            self._pin(
                extract_segment(upd["cache"], seg_len, self._scan_layers)
            )
            if grow
            else ()
        )
        new_state = {
            "cache": cache,
            "last_tok": state["last_tok"].at[slot].set(first[0]),
            "keys": state["keys"].at[slot].set(key),
            "remaining": state["remaining"].at[slot].set(max_new - 1),
        }
        if self._spec:
            new_state.update(_seed_history(
                state, full, p_len, slot, first[0]
            ))
        if self._adapters:
            new_state["adapter_ids"] = state["adapter_ids"].at[slot].set(
                jnp.asarray(aid, jnp.int32)
            )
        return new_state, first[0], seg

    def _chunk_step_fn(self, params, cache1, tokens, aid=0):
        """One mid-prompt prefill chunk (chunked prefill, ISSUE 11):
        the same chunked decode continuation the splice path relies on,
        over exactly ``prefill_chunk`` tokens, batch-1 side cache in ->
        side cache out. No sampling, no slot surgery, no fetch — the
        call is one async dispatch, so a long prompt costs its
        co-scheduled slots one chunk of device time per step, never the
        whole prompt. ``last_pos=0`` keeps the dead lm-head gather
        trivial (mid-chunk logits are never consumed)."""
        kw = {}
        if self._adapters:
            kw["adapter_ids"] = jnp.asarray(aid, jnp.int32)
        _, upd = self.model.apply(
            {"params": params, "cache": cache1}, tokens, decode=True,
            mutable=["cache"], last_pos=0, **kw,
        )
        return self._pin(upd["cache"])

    def _chunk_final_fn(self, params, cache1, state, suffix, full,
                        last_local, p_len, slot, seed, max_new, aid=0,
                        *, seg_len, grow):
        """Final chunk of a chunked prefill: identical math to
        :meth:`_splice_fn` except the batch-1 start cache arrives as an
        ARGUMENT (the accumulated side cache) instead of being seeded
        from a retained segment. With ``grow`` the FULL prompt's segment
        rides out for insertion — the side cache holds every position,
        so chunked prompts deepen the prefix index exactly like whole
        prefills do. ``seg_len``/``grow`` static, same bucket discipline
        as the splice; ``last_local`` is the final chunk's last REAL
        token position (traced)."""
        kw = {}
        if self._adapters:
            kw["adapter_ids"] = jnp.asarray(aid, jnp.int32)
        return self._finish_prefill(
            params, cache1, state, suffix, last_local, full,
            p_len, slot, seed, max_new, aid, kw, seg_len, grow,
        )

    # -- paged twins (ISSUE 13) --------------------------------------------

    def _prefill_paged_fn(self, params, state, tokens, row, p_len, slot,
                          seed, max_new, aid=0):
        """Paged-engine prefill: the forward is the SAME unpaged batch-1
        prefill as :meth:`_prefill_fn` (self.model — prefill math never
        pages), then :func:`.slots.write_slot_paged` scatters the full
        window into the pool pages named by ``row`` (the slot's new page
        table, sentinel-padded past its allocation) and installs the row
        at ``slot``. The full-row scatter doubles as the recycled-page
        sanitizer: any junk a completed slot's in-flight chains wrote
        through these page ids dispatched BEFORE this program, so
        program order guarantees the pages hold exactly this prompt's
        K/V afterwards. No segment extraction — paged prefix retention
        pins page ids host-side (``_insert_paged_segment``), zero device
        work. ``row`` is a traced (pages_per_slot,) int32 vector; one
        compile per prompt bucket, exactly like the classic twin."""
        kw = {}
        if self._adapters:
            kw["adapter_ids"] = jnp.asarray(aid, jnp.int32)
        logits, upd = self.model.apply(
            {"params": params}, tokens, prefill=True, mutable=["cache"],
            last_pos=p_len - 1, **kw,
        )
        key = jax.random.PRNGKey(seed)
        first, key = sample_logits(
            logits[:, -1].astype(jnp.float32), key,
            self._temperature, self._top_k, self._top_p,
        )
        cache = self._pin(write_slot_paged(
            state["cache"], upd["cache"], row, slot, p_len,
            self._page_size, self._scan_layers,
        ))
        new_state = {
            "cache": cache,
            "last_tok": state["last_tok"].at[slot].set(first[0]),
            "keys": state["keys"].at[slot].set(key),
            "remaining": state["remaining"].at[slot].set(max_new - 1),
        }
        if self._spec:
            new_state.update(_seed_history(
                state, tokens, p_len, slot, first[0]
            ))
        if self._adapters:
            new_state["adapter_ids"] = state["adapter_ids"].at[slot].set(
                jnp.asarray(aid, jnp.int32)
            )
        return new_state, first[0]

    def _splice_paged_fn(self, params, state, row, suffix, full, depth,
                         p_len, slot, seed, max_new, cow_src, cow_dst,
                         aid=0):
        """Paged prefix-cache-hit refill: O(suffix) HBM instead of the
        classic segment copy. The donor's FULL shared pages (indices
        ``< depth // page_size`` in ``row``) are referenced in place —
        never copied, never written (all new writes land at positions
        ``>= depth``, i.e. page index ``>= depth // page_size``). A
        partially-shared boundary page is copy-on-written: ``cow_src``
        (the donor's page) is gathered and scattered whole into
        ``cow_dst`` (a fresh page already at ``row[depth//page_size]``);
        positions beyond ``depth`` in the copy are the donor's stale
        tail, overwritten by this suffix prefill's stores (which precede
        attention reads) or masked by the validity row — the exact
        stale-tail argument the classic splice rests on. When ``depth``
        is page-aligned both ids arrive as the sentinel (pool_pages) and
        the gather/scatter no-op via fill/drop, so ONE compiled shape
        serves both cases.

        The suffix forward runs through ``self._dec_model`` over a
        batch-1 VIEW of the live pool: page_table = ``row``, cache_index
        = ``depth``, pool leaves shared — suffix K/V streams DIRECTLY
        into the slot's pages through the table. The merge-back installs
        ``row``/``p_len`` at ``slot`` and keeps the updated pool;
        everything else follows :meth:`_finish_prefill`. All page
        geometry is traced DATA (no static argnames): compiles stay one
        per suffix bucket."""
        kw = {}
        if self._adapters:
            kw["adapter_ids"] = jnp.asarray(aid, jnp.int32)
        ax = 1 if self._scan_layers else 0
        src = jnp.asarray([cow_src], jnp.int32)
        dst = jnp.asarray([cow_dst], jnp.int32)

        def cow(path, leaf):
            name = _leaf_name(path)
            if name not in _POOL_TO_FLAT:
                return leaf
            page = jnp.take(leaf, src, axis=ax, mode="fill", fill_value=0)
            if self._scan_layers:
                return leaf.at[:, dst].set(page, mode="drop")
            return leaf.at[dst].set(page, mode="drop")

        cache = jax.tree_util.tree_map_with_path(cow, state["cache"])
        p_cap = self._pages_per_slot

        def view(path, leaf):
            name = _leaf_name(path)
            if name == "page_table":
                return jnp.broadcast_to(
                    row, leaf.shape[:-2] + (1, p_cap)
                ).astype(jnp.int32)
            if name == "cache_index":
                return jnp.full(leaf.shape[:-1] + (1,), depth, jnp.int32)
            return leaf

        cache1 = jax.tree_util.tree_map_with_path(view, cache)
        logits, upd = self._dec_model.apply(
            {"params": params, "cache": cache1}, suffix, decode=True,
            mutable=["cache"], last_pos=p_len - 1 - depth, **kw,
        )
        key = jax.random.PRNGKey(seed)
        first, key = sample_logits(
            logits[:, -1].astype(jnp.float32), key,
            self._temperature, self._top_k, self._top_p,
        )

        def merge(path, big, new1):
            name = _leaf_name(path)
            if name == "page_table":
                return big.at[..., slot, :].set(
                    jnp.asarray(row, big.dtype)
                )
            if name == "cache_index":
                # the view's counter advanced by the suffix bucket; the
                # slot's true position is p_len, same as classic splice
                return big.at[..., slot].set(
                    jnp.asarray(p_len, big.dtype)
                )
            return new1  # pool leaf: the updated pool IS the new pool

        cache = self._pin(jax.tree_util.tree_map_with_path(
            merge, cache, upd["cache"]
        ))
        new_state = {
            "cache": cache,
            "last_tok": state["last_tok"].at[slot].set(first[0]),
            "keys": state["keys"].at[slot].set(key),
            "remaining": state["remaining"].at[slot].set(max_new - 1),
        }
        if self._spec:
            new_state.update(_seed_history(
                state, full, p_len, slot, first[0]
            ))
        if self._adapters:
            new_state["adapter_ids"] = state["adapter_ids"].at[slot].set(
                jnp.asarray(aid, jnp.int32)
            )
        return new_state, first[0]

    def _paged_park_fn(self, state, slot):
        """Sentinel ``slot``'s page-table row and zero its budget. Paged
        engines park on EVERY completion (classic ones only when budget
        remains): an inactive slot still K/V-writes at advancing
        positions each chain step, and through a live table those writes
        would land in pages the host has already freed — or handed to a
        prefix segment. Sentinel ids turn them into ``mode="drop"``
        no-ops for every chain dispatched after this program; writes
        from chains already in flight (pipelining) are sanitized by the
        next allocation's full-row prefill scatter, which the device
        runs after them in program order."""
        def upd(path, leaf):
            name = _leaf_name(path)
            if name == "page_table":
                return leaf.at[..., slot, :].set(self._pool_pages)
            return leaf

        new_state = dict(state)
        new_state["cache"] = jax.tree_util.tree_map_with_path(
            upd, state["cache"]
        )
        new_state["remaining"] = state["remaining"].at[slot].set(0)
        return new_state

    def _chunk_seed_paged_fn(self, cache, row, depth):
        """Paged seed for a chunked-prefill prefix hit: gather-COPY the
        donor's pages (``row``: ``ceil(depth/page_size)`` real ids,
        sentinel-padded to the fixed table length) out of the live pool
        into the UNPAGED batch-1 side cache the chunk steps accumulate
        through — the paged analogue of :func:`.slots.seed_cache`.
        Chunked prompts then prefill into all-fresh pages at the final
        scatter (sharing is lost for them; the copy here is what buys
        the reused-prefix FLOPs back). A partially-covered boundary page
        copies whole — its tail past ``depth`` is donor-stale, dead
        under the continuation's stores-then-reads order, the same
        argument as the paged splice. Sentinel rows gather as zeros,
        matching the zero-init the classic side cache starts from."""
        ax = 1 if self._scan_layers else 0
        flat = {
            tuple(
                str(getattr(k, "key", getattr(k, "idx", k)))
                for k in path
            ): leaf
            for path, leaf in
            jax.tree_util.tree_leaves_with_path(cache)
        }
        flat_to_pool = {v: k for k, v in _POOL_TO_FLAT.items()}

        def build(path, proto):
            name = _leaf_name(path)
            if name == "cache_index":
                return jnp.full(proto.shape, depth, jnp.int32)
            pkey = tuple(
                str(getattr(k, "key", getattr(k, "idx", k)))
                for k in path
            )[:-1] + (flat_to_pool[name],)
            g = jnp.take(
                flat[pkey], row, axis=ax, mode="fill", fill_value=0
            )
            if self._scan_layers:
                out = g.reshape((g.shape[0], 1, -1) + g.shape[3:])
            else:
                out = g.reshape((1, -1) + g.shape[2:])
            return out.astype(proto.dtype)

        return self._pin(
            jax.tree_util.tree_map_with_path(build, self._proto1)
        )

    def _chunk_final_paged_fn(self, params, cache1, state, suffix, full,
                              last_local, p_len, slot, seed, max_new,
                              row, aid=0):
        """Paged final chunk: the same decode continuation as
        :meth:`_chunk_final_fn` over the accumulated side cache, then
        :func:`.slots.write_slot_paged` scatters the whole window into
        the slot's fresh pages (``row``) — full-row, so it sanitizes
        recycled pages exactly like the paged prefill does. No segment
        rides out (paged retention pins page ids host-side)."""
        kw = {}
        if self._adapters:
            kw["adapter_ids"] = jnp.asarray(aid, jnp.int32)
        logits, upd = self.model.apply(
            {"params": params, "cache": cache1}, suffix, decode=True,
            mutable=["cache"], last_pos=last_local, **kw,
        )
        key = jax.random.PRNGKey(seed)
        first, key = sample_logits(
            logits[:, -1].astype(jnp.float32), key,
            self._temperature, self._top_k, self._top_p,
        )
        cache = self._pin(write_slot_paged(
            state["cache"], upd["cache"], row, slot, p_len,
            self._page_size, self._scan_layers,
        ))
        new_state = {
            "cache": cache,
            "last_tok": state["last_tok"].at[slot].set(first[0]),
            "keys": state["keys"].at[slot].set(key),
            "remaining": state["remaining"].at[slot].set(max_new - 1),
        }
        if self._spec:
            new_state.update(_seed_history(
                state, full, p_len, slot, first[0]
            ))
        if self._adapters:
            new_state["adapter_ids"] = state["adapter_ids"].at[slot].set(
                jnp.asarray(aid, jnp.int32)
            )
        return new_state, first[0]

    # -- disaggregation twins (ISSUE 18) -----------------------------------

    def _handoff_prefill_fn(self, params, tokens, p_len, seed, aid=0):
        """Prefill-role miss path: the SAME batched prefill forward as
        :meth:`_prefill_fn`, but instead of slot surgery the whole
        prompt-bucket batch-1 cache rides out as a transferable segment
        (:func:`.slots.extract_segment` over ``tokens.shape[1]`` — one
        compile per pow2 bucket, the prefix-splice discipline). Returns
        ``(segment, first, key)``, ALL device residents: the sampled
        first token and the post-sample PRNG key travel with the
        segment so the decode side continues the request's stream
        exactly where a monolithic engine would. No fetch happens on
        this engine, ever — the prefill-role budget is ZERO, pinned by
        the device_get spy in tests/test_serve.py."""
        kw = {}
        if self._adapters:
            kw["adapter_ids"] = jnp.asarray(aid, jnp.int32)
        logits, upd = self.model.apply(
            {"params": params}, tokens, prefill=True, mutable=["cache"],
            last_pos=p_len - 1, **kw,
        )
        key = jax.random.PRNGKey(seed)
        first, key = sample_logits(
            logits[:, -1].astype(jnp.float32), key,
            self._temperature, self._top_k, self._top_p,
        )
        seg = self._pin(extract_segment(
            upd["cache"], tokens.shape[1], self._scan_layers
        ))
        return seg, first[0], key

    def _handoff_splice_fn(self, params, segment, suffix, depth, p_len,
                           seed, aid=0, *, seg_len):
        """Prefill-role prefix-hit path: seed from the retained donor
        at ``depth`` and run the chunked decode continuation over the
        uncached suffix (the same bitwise-equal-to-prefill math
        :meth:`_splice_fn` uses), then extract the FULL prompt bucket
        as the outgoing segment. ``seg_len`` is static — the pow2
        bucket set keeps compiles bounded, never per request."""
        kw = {}
        if self._adapters:
            kw["adapter_ids"] = jnp.asarray(aid, jnp.int32)
        cache1 = self._pin(seed_cache(self._proto1, segment, depth))
        return self._handoff_from_cache(
            params, cache1, suffix, p_len - 1 - depth, seed, kw, seg_len
        )

    def _handoff_final_fn(self, params, cache1, suffix, last_local,
                          seed, aid=0, *, seg_len):
        """Prefill-role final chunk of a chunked prefill: the decode
        continuation over the accumulated side cache, ending in segment
        extraction instead of slot surgery (the :meth:`_chunk_final_fn`
        analogue — long prompts stream through the SAME exact-N mid
        chunks on a prefill-role engine, so a disaggregated fleet keeps
        the no-prefill-freeze property)."""
        kw = {}
        if self._adapters:
            kw["adapter_ids"] = jnp.asarray(aid, jnp.int32)
        return self._handoff_from_cache(
            params, cache1, suffix, last_local, seed, kw, seg_len
        )

    def _handoff_from_cache(self, params, cache1, suffix, last_local,
                            seed, kw, seg_len):
        """Shared tail of the prefill-role splice / final-chunk
        programs: continuation forward, first-token sample, full-bucket
        segment extraction. A plain helper traced inline by its
        callers, same pattern as :meth:`_finish_prefill`."""
        logits, upd = self.model.apply(
            {"params": params, "cache": cache1}, suffix, decode=True,
            mutable=["cache"], last_pos=last_local, **kw,
        )
        key = jax.random.PRNGKey(seed)
        first, key = sample_logits(
            logits[:, -1].astype(jnp.float32), key,
            self._temperature, self._top_k, self._top_p,
        )
        seg = self._pin(extract_segment(
            upd["cache"], seg_len, self._scan_layers
        ))
        return seg, first[0], key

    def _accept_fn(self, params, state, segment, full, first, key,
                   p_len, slot, max_new, aid=0):
        """Decode-role accept: rebuild the monolithic post-prefill slot
        state from a transferred segment. ``seed_cache`` zero-fills the
        batch-1 proto and lands the segment at the origin — positions
        ``[0, bucket)`` then hold exactly what the prefill forward
        wrote (pad-position K/V included) and everything beyond is
        zero, which is bitwise what ``upd["cache"]`` looked like on the
        prefill engine — and ``write_slot`` performs the IDENTICAL
        splice :meth:`_prefill_fn` would have. Disaggregated
        token-exactness is therefore BITWISE for every cache family
        (int8/int4 included: nothing is recomputed, so quantization
        never reassociates). ``first``/``key`` arrive as device
        residents from the :class:`..serve.scheduler.Handoff`;
        ``params`` is unused but keeps ``state`` at donate index 1 (the
        segment, arg 2, is NEVER donated — the router may re-dispatch
        it). ``full`` is the bucket-padded prompt seeding the n-gram
        history — a dead operand when speculation is off, exactly like
        :meth:`_splice_fn`'s."""
        del params  # decode accept recomputes nothing
        cache1 = self._pin(seed_cache(self._proto1, segment, p_len))
        cache = self._pin(write_slot(
            state["cache"], cache1, slot, p_len, self._scan_layers
        ))
        new_state = {
            "cache": cache,
            "last_tok": state["last_tok"].at[slot].set(first),
            "keys": state["keys"].at[slot].set(key),
            "remaining": state["remaining"].at[slot].set(max_new - 1),
        }
        if self._spec:
            new_state.update(_seed_history(
                state, full, p_len, slot, first
            ))
        if self._adapters:
            new_state["adapter_ids"] = state["adapter_ids"].at[slot].set(
                jnp.asarray(aid, jnp.int32)
            )
        return new_state, first

    def _accept_paged_fn(self, params, state, segment, full, row, first,
                         key, p_len, slot, max_new, aid=0):
        """Paged decode-role accept: reconstruct the batch-1 cache as
        in :meth:`_accept_fn`, then scatter it into the slot's fresh
        pages (:func:`.slots.write_slot_paged` — full-row, so it
        sanitizes recycled pages exactly like the paged prefill does).
        Page geometry rides as the traced ``row`` vector; one compile
        per segment bucket."""
        del params
        cache1 = self._pin(seed_cache(self._proto1, segment, p_len))
        cache = self._pin(write_slot_paged(
            state["cache"], cache1, row, slot, p_len,
            self._page_size, self._scan_layers,
        ))
        new_state = {
            "cache": cache,
            "last_tok": state["last_tok"].at[slot].set(first),
            "keys": state["keys"].at[slot].set(key),
            "remaining": state["remaining"].at[slot].set(max_new - 1),
        }
        if self._spec:
            new_state.update(_seed_history(
                state, full, p_len, slot, first
            ))
        if self._adapters:
            new_state["adapter_ids"] = state["adapter_ids"].at[slot].set(
                jnp.asarray(aid, jnp.int32)
            )
        return new_state, first

    # -- SLO preemption twins (ISSUE 20) -----------------------------------

    def _swap_leaves(self, state, slot, segment):
        """Shared tail of the swap-out programs: bundle the segment with
        the slot's sampling leaves (next decode input, PRNG stream
        mid-sequence, and the n-gram history when speculation is on) so
        the host parks EVERYTHING the swap-in needs behind ONE batched
        fetch — the swap's single budgeted ``device_get``."""
        out = {
            "segment": segment,
            "last_tok": state["last_tok"][slot],
            "key": state["keys"][slot],
        }
        if self._spec:
            out["hist"] = state["hist"][slot]
            out["hist_len"] = state["hist_len"][slot]
        return out

    def _swap_out_fn(self, state, slot, *, seg_len):
        """Swap-out (whole-slot): cut slot ``slot``'s cache down to a
        batch-1 tree (``dynamic_slice_in_dim`` along the slot axis —
        slot is traced, no per-slot compiles) and extract positions
        ``[0, seg_len)`` — the Handoff extraction pointed at host: the
        segment covers every position the slot has WRITTEN (``seg_len``
        is the static pow2 bucket of the current position, same compile
        family as prefill), so re-splicing it via ``seed_cache`` +
        ``write_slot`` rebuilds the slot bitwise — nothing is
        recomputed, so quantized caches round-trip exactly too. Reads
        live state (never donated): the host re-checks the victim after
        draining the pipeline and may keep it decoding."""

        def cut(path, leaf):
            if _leaf_name(path) == "cache_index":
                return jax.lax.dynamic_slice_in_dim(
                    leaf, slot, 1, axis=leaf.ndim - 1
                )
            ax = 1 if self._scan_layers else 0
            return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=ax)

        cache1 = jax.tree_util.tree_map_with_path(cut, state["cache"])
        return self._swap_leaves(state, slot, extract_segment(
            cache1, seg_len, self._scan_layers
        ))

    def _swap_out_paged_fn(self, state, row, slot, position, *, seg_len):
        """Paged swap-out: gather the slot's pool pages (``row``: its
        live page table, sentinel-padded) into the unpaged batch-1
        layout — the :meth:`_chunk_seed_paged_fn` gather reused as an
        extractor — then cut the position bucket exactly like the
        whole-slot twin. The pages themselves return to the pool on the
        host side the moment the fetch lands; this program only reads
        them."""
        cache1 = self._chunk_seed_paged_fn(state["cache"], row, position)
        return self._swap_leaves(state, slot, extract_segment(
            cache1, seg_len, self._scan_layers
        ))

    def _swap_in_fn(self, params, state, segment, last_tok, key,
                    position, slot, remaining, hist=None, hist_len=None,
                    aid=0):
        """Swap-in (whole-slot): the :meth:`_accept_fn` splice pointed
        at a host-parked segment — ``seed_cache`` + ``write_slot``
        rebuild the preempted slot at ``position`` bitwise (nothing
        recomputed: the disaggregation argument verbatim), and the
        sampling leaves restore VERBATIM instead of being re-seeded:
        ``remaining`` is the request's live budget (not ``max_new - 1``)
        and ``key`` the PRNG stream mid-sequence, so the resumed
        request's tokens are exactly the undisturbed run's. ``params``
        is unused but keeps ``state`` at donate index 1."""
        del params  # swap-in recomputes nothing
        cache1 = self._pin(seed_cache(self._proto1, segment, position))
        cache = self._pin(write_slot(
            state["cache"], cache1, slot, position, self._scan_layers
        ))
        return self._swap_in_rest(
            state, cache, last_tok, key, slot, remaining, hist,
            hist_len, aid,
        )

    def _swap_in_paged_fn(self, params, state, segment, row, last_tok,
                          key, position, slot, remaining, hist=None,
                          hist_len=None, aid=0):
        """Paged swap-in: scatter the rebuilt batch-1 cache into the
        slot's FRESH pages (``write_slot_paged`` full-row — sanitizing,
        like every paged refill); page ids were re-allocated host-side,
        so a resumed request may land on different physical pages than
        it held — invisible in the tokens, the page table is DATA."""
        del params
        cache1 = self._pin(seed_cache(self._proto1, segment, position))
        cache = self._pin(write_slot_paged(
            state["cache"], cache1, row, slot, position,
            self._page_size, self._scan_layers,
        ))
        return self._swap_in_rest(
            state, cache, last_tok, key, slot, remaining, hist,
            hist_len, aid,
        )

    def _swap_in_rest(self, state, cache, last_tok, key, slot,
                      remaining, hist, hist_len, aid):
        """Shared bookkeeping tail of the swap-in programs."""
        new_state = {
            "cache": cache,
            "last_tok": state["last_tok"].at[slot].set(last_tok),
            "keys": state["keys"].at[slot].set(key),
            "remaining": state["remaining"].at[slot].set(remaining),
        }
        if self._spec:
            new_state["hist"] = state["hist"].at[slot].set(
                hist.astype(state["hist"].dtype)
            )
            new_state["hist_len"] = state["hist_len"].at[slot].set(
                hist_len
            )
        if self._adapters:
            new_state["adapter_ids"] = state["adapter_ids"].at[slot].set(
                jnp.asarray(aid, jnp.int32)
            )
        return new_state

    def _chain_fn(self, params, state):
        """``tokens_per_launch`` decode steps as one ``lax.scan`` — one
        launch, one (S, T) token block out. Every slot steps every time
        (fixed shapes); inactive slots re-emit their last token. Before
        each step their depth is set to the window
        (``park_cache_index``): their K/V writes drop
        (``_store_decode_kv`` in models/transformer.py), the decode
        kernel reads no row of them, what the plain path computes for
        them is never consumed, and refill rewrites the whole slot
        anyway.

        With the adapter bank on, the per-slot adapter-id vector rides
        into every step as a scan CONSTANT (refill — the only writer —
        runs between chains), and each step's forward gathers each
        slot's factors by it (:func:`..adapters.bank.apply_lora`):
        heterogeneous tenants decode together in this one program.

        With ``guard_nonfinite`` the scan ALSO emits a per-slot
        per-step finite-logits flag (an ``isfinite`` reduction over the
        logits row — the flag is DATA, the host reads it from the
        chain's one batched fetch, never branches on it in here): the
        poison-slot quarantine signal. Guard off, the emitted pytree —
        and the whole jaxpr — is byte-identical to the pre-guard
        chain."""
        return self._chain_impl(params, state, None)

    def _chain_chaos_fn(self, params, state, chain_base):
        """Chaos twin of :meth:`_chain_fn`: ``chain_base`` (a traced
        scalar, ``n_chains * tokens_per_launch`` at dispatch) gives the
        injector a global decode-step index so a configured NaN lands
        at exactly one (slot, step) — deterministic, recompile-free."""
        return self._chain_impl(params, state, chain_base)

    def _chain_impl(self, params, state, chain_base):
        kw = (
            {"adapter_ids": state["adapter_ids"]}
            if self._adapters else {}
        )
        guard = self._guard

        def step(carry, x):
            cache, tok, keys, remaining = carry
            active = remaining > 0
            # a slot without budget holds no live sequence: its depth reads
            # "nothing here" (the window: its writes drop, and the decode
            # kernel reads no row of it) until a refill writes a real one
            cache = park_cache_index(cache, ~active, self.window)
            # _dec_model IS self.model unless paged (then it's the
            # pool+page-table twin) — unpaged chains trace byte-identical
            logits, upd = self._dec_model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                decode=True, mutable=["cache"], **kw,
            )
            row = logits[:, -1].astype(jnp.float32)
            if chain_base is not None:
                row = chaos_lib.poison_logits(
                    row, chain_base + x,
                    self._chaos.nan_logit_slot, self._chaos.nan_logit_step,
                )
            nxt, keys = sample_logits_per_slot(
                row, keys,
                self._temperature, self._top_k, self._top_p,
            )
            nxt = jnp.where(active, nxt, tok)
            remaining = remaining - active.astype(remaining.dtype)
            out = (
                (nxt, jnp.all(jnp.isfinite(row), axis=-1))
                if guard else nxt
            )
            return (self._pin(upd["cache"]), nxt, keys, remaining), out

        carry = (
            state["cache"], state["last_tok"], state["keys"],
            state["remaining"],
        )
        xs = (
            jnp.arange(self.tokens_per_launch)
            if chain_base is not None else None
        )
        (cache, tok, keys, remaining), outs = jax.lax.scan(
            step, carry, xs, length=self.tokens_per_launch
        )
        out = {
            "cache": cache, "last_tok": tok, "keys": keys,
            "remaining": remaining,
        }
        if self._adapters:
            out["adapter_ids"] = state["adapter_ids"]
        if guard:
            toks, oks = outs
            # (n_slots, tokens_per_launch) tokens + finite flags, ONE
            # fetched pytree — the budget is still one fetch per chain
            return out, (toks.T, oks.T)
        return out, outs.T  # (n_slots, tokens_per_launch)

    def _spec_chain_fn(self, params, state):
        """Speculate-k decode chain: ``tokens_per_launch`` iterations of
        draft -> verify -> accept/rewind, one ``lax.scan``, one launch.

        Per iteration every slot (a) drafts ``k`` tokens via longest
        n-gram suffix match over its history buffer
        (:func:`..models.sampling.ngram_draft` — fixed-shape gather/
        compare, no host round-trip), (b) verifies ``[last_tok, drafts]``
        in ONE (S, k+1) decode forward — the chunked-continuation path,
        so logits at position i condition on drafts < i exactly as
        sequential decode would, (c) accepts the longest matching prefix
        plus the standard bonus/rejection token
        (:func:`..models.sampling.speculative_accept`) and REWINDS each
        slot's position counter by the rejected count
        (:func:`..models.transformer.rewind_cache_index` — the forward
        advanced all counters by k+1; stale K/V at rejected positions is
        overwritten by the next iteration's writes before any query can
        attend there, and out-of-window writes drop via the
        ``mode="drop"`` scatter).

        Accepted length is DATA: shapes never depend on it, so one
        compile serves every acceptance pattern. The chain emits a fixed
        (S, T, k+1) token block + (S, T) per-step emit counts; inactive
        slots emit count 0 and their history is untouched (their scatter
        columns clamp out via ``mode="drop"``). ``guard_nonfinite``
        appends a per-slot per-step finite flag over the (k+1, V) verify
        logits, same contract as :meth:`_chain_fn`."""
        return self._spec_chain_impl(params, state, None)

    def _spec_chain_chaos_fn(self, params, state, chain_base):
        """Chaos twin of :meth:`_spec_chain_fn` (``chain_base`` counts
        scan ITERATIONS across chains — each iteration verifies k+1
        positions, so the step index is per-verify, not per-token)."""
        return self._spec_chain_impl(params, state, chain_base)

    def _spec_chain_impl(self, params, state, chain_base):
        k = self._spec_k
        rows = jnp.arange(self.n_slots)
        offs = jnp.arange(k + 1)
        win = self.window
        guard = self._guard
        # same scan-constant contract as _chain_fn
        kw = (
            {"adapter_ids": state["adapter_ids"]}
            if self._adapters else {}
        )

        def step(carry, x):
            cache, tok, keys, remaining, hist, hist_len = carry
            active = remaining > 0
            draft = ngram_draft(hist, hist_len, k, self._spec_ngram)
            toks_in = jnp.concatenate([tok[:, None], draft], axis=1)
            logits, upd = self._dec_model.apply(
                {"params": params, "cache": cache}, toks_in,
                decode=True, mutable=["cache"], **kw,
            )
            lg = logits.astype(jnp.float32)
            if chain_base is not None:
                lg = chaos_lib.poison_logits(
                    lg, chain_base + x,
                    self._chaos.nan_logit_slot, self._chaos.nan_logit_step,
                )
            emitted, n_acc, keys = speculative_accept(
                lg, draft, keys,
                self._temperature, self._top_k, self._top_p,
            )
            # the verify forward advanced every counter by k+1; the slot
            # really produced 1 + n_acc tokens, so rewind the rest
            cache = self._pin(rewind_cache_index(upd["cache"], k - n_acc))
            n_emit = jnp.where(active, n_acc + 1, 0).astype(jnp.int32)
            new_tok = jnp.where(active, emitted[rows, n_acc], tok)
            cols = jnp.where(
                offs[None, :] < n_emit[:, None],
                hist_len[:, None] + offs[None, :], win,
            )
            hist = hist.at[rows[:, None], cols].set(
                emitted, mode="drop"
            )
            hist_len = jnp.minimum(hist_len + n_emit, win)
            remaining = jnp.maximum(
                remaining - n_emit, 0
            ).astype(remaining.dtype)
            carry = (cache, new_tok, keys, remaining, hist, hist_len)
            out = (emitted, n_emit)
            if guard:
                out = out + (jnp.all(jnp.isfinite(lg), axis=(1, 2)),)
            return carry, out

        carry = (
            state["cache"], state["last_tok"], state["keys"],
            state["remaining"], state["hist"], state["hist_len"],
        )
        xs = (
            jnp.arange(self.tokens_per_launch)
            if chain_base is not None else None
        )
        (cache, tok, keys, remaining, hist, hist_len), outs = (
            jax.lax.scan(step, carry, xs, length=self.tokens_per_launch)
        )
        out = {
            "cache": cache, "last_tok": tok, "keys": keys,
            "remaining": remaining, "hist": hist, "hist_len": hist_len,
        }
        if self._adapters:
            out["adapter_ids"] = state["adapter_ids"]
        if guard:
            toks, counts, oks = outs
            return out, (
                jnp.transpose(toks, (1, 0, 2)), counts.T, oks.T
            )
        toks, counts = outs
        # (S, T, k+1) token block + (S, T) counts
        return out, (jnp.transpose(toks, (1, 0, 2)), counts.T)

    # ------------------------------------------------------------------
    # host-side driver
    # ------------------------------------------------------------------

    def submit(self, request: Request) -> int:
        """Enqueue one request; returns its id. Raises
        :class:`..serve.scheduler.QueueFull` when the bounded queue is at
        capacity (backpressure), :class:`..serve.scheduler.QueueClosed`
        after :meth:`close` (shutdown), or ``ValueError`` when the
        request can never fit the window — or names an adapter this
        engine cannot serve (no bank, or an unregistered/out-of-range
        id): admission failures are always synchronous, never a
        mid-decode surprise.

        Admission also snapshots the adapter row's tenant-generation
        (rows recycle): :meth:`_refill` re-checks it, so a request whose
        tenant is evicted — or whose row is handed to a NEW tenant —
        while it queues completes as ``"adapter_evicted"`` instead of
        silently decoding under someone else's factors."""
        if self._role == "decode":
            raise ValueError(
                "role='decode' engines admit work via accept(request, "
                "handoff), not submit() — a prompt with no finished "
                "prefill attached has nothing to decode from"
            )
        return self._admit(request)

    def _admit(self, request: Request) -> int:
        """Shared admission body of :meth:`submit` and :meth:`accept`:
        adapter + paged checks, scheduler enqueue, flight stamp — all
        inside the ``prog:submit`` profiler span."""
        with annotate(
            "submit", p_len=len(request.prompt),
            max_new=request.max_new_tokens,
        ) as span:
            rid = self._admit_checked(request)
            span.set_metadata(rid=rid)
        return rid

    def _admit_checked(self, request: Request) -> int:
        aid = int(getattr(request, "adapter", 0))
        if aid != 0 and not self._adapters:
            raise ValueError(
                f"request names adapter {aid} but the engine has no "
                "adapter bank (pass ServeEngine(adapter_bank=...))"
            )
        if self._adapters:
            self._bank.check_id(aid)
            request.adapter_gen = self._bank.generation(aid)
        if self._paged:
            # paged admission (ISSUE 13): a request whose prompt+budget
            # needs more pages than the whole pool holds can NEVER be
            # scheduled — synchronous backpressure, same contract as
            # QueueFull. (Transient pressure is different: a request
            # that fits the pool but not the current free list just
            # stays queued — _pop_request skips it until pages free.)
            need = self._pool.pages_needed(
                len(request.prompt) + request.max_new_tokens
            )
            if need > self._pool.pool_pages:
                self._pool.shed()
                if self._flight is not None:
                    self._flight.record(
                        "pool_shed", p_len=len(request.prompt),
                        max_new=request.max_new_tokens, pages=need,
                    )
                raise PoolExhausted(
                    f"request needs {need} pages but the pool holds "
                    f"{self._pool.pool_pages} "
                    f"({self._pool.page_size} tokens each) — shrink the "
                    "request or grow the pool"
                )
        rid = self.scheduler.submit(request)
        if self._flight is not None:
            # stamped AFTER admission: rejected submissions never open a
            # span (the caller got a synchronous exception instead)
            self._flight.request_submitted(
                rid, p_len=len(request.prompt),
                max_new=request.max_new_tokens, adapter=aid,
            )
        return rid

    def accept(self, request: Request, handoff: Handoff) -> int:
        """Decode-role admission: enqueue ``request`` with its finished
        prefill attached. The segment is validated against THIS
        engine's cache layout first (heterogeneous fleets differ in
        window / slot count per role — a mismatched segment must fail
        here, synchronously, never inside a compiled program); adapter
        and paged admission then run exactly as :meth:`submit`'s. The
        handoff's original ``submitted_s`` is restored after the
        scheduler re-stamps, so latency / TTFT span the ORIGINAL
        submit on the prefill side, not the transfer."""
        if self._role != "decode":
            raise ValueError(
                "accept() needs role='decode' — monolithic and "
                "prefill-role engines take work via submit()"
            )
        self._validate_segment(handoff.segment)
        rid = self._admit(request)
        if handoff.submitted_s:
            request.submitted_s = handoff.submitted_s
        self._handoff_in[rid] = handoff
        return rid

    def take_handoff(self, request_id: int) -> Handoff:
        """Pop the finished :class:`..serve.scheduler.Handoff` a
        prefill-role engine emitted for ``request_id`` (the router
        calls this when it sees the ``"handoff"`` completion). The
        record leaves this engine's ownership — device buffers stay
        alive through the handoff's own references."""
        if self._role != "prefill":
            raise ValueError(
                "take_handoff() needs role='prefill' — only prefill-"
                "role engines emit handoffs"
            )
        return self._handoffs.pop(request_id)

    def _validate_segment(self, segment) -> None:
        """Admission check for a transferred segment: the tree must
        have THIS engine's batch-1 cache structure (dtype + rank per
        leaf — a different KV quantization family is a different
        structure and fails here) and fit the serving window (at most
        one axis may differ from the window-length proto, and only
        downward)."""
        p_leaves, p_def = jax.tree_util.tree_flatten(self._proto1)
        leaves, tdef = jax.tree_util.tree_flatten(segment)
        if tdef != p_def:
            raise ValueError(
                "handoff segment does not match this engine's cache "
                "layout (different model config or KV cache family?)"
            )
        for leaf, proto in zip(leaves, p_leaves):
            if leaf.dtype != proto.dtype or leaf.ndim != proto.ndim:
                raise ValueError(
                    f"handoff segment leaf {leaf.dtype}/{leaf.ndim}d "
                    f"does not match this engine's "
                    f"{proto.dtype}/{proto.ndim}d cache leaf"
                )
            diff = [
                i for i in range(leaf.ndim)
                if leaf.shape[i] != proto.shape[i]
            ]
            if len(diff) > 1 or (
                diff and leaf.shape[diff[0]] > proto.shape[diff[0]]
            ):
                raise ValueError(
                    f"handoff segment leaf shape {leaf.shape} does not "
                    f"fit this engine's window (proto {proto.shape})"
                )

    @property
    def role(self) -> str | None:
        return self._role

    @property
    def active_slots(self) -> int:
        return sum(a is not None for a in self._slots)

    @property
    def idle(self) -> bool:
        return (
            self.active_slots == 0
            and len(self.scheduler) == 0
            and not self._pending
            and not self._inflight
            and not self._handoff_in
        )

    @property
    def load(self) -> int:
        """Host-visible backlog: active + pending + queued + accepted
        handoffs awaiting a slot. The router's least-loaded decode
        placement key (ISSUE 18) — pure host counting, no fetch."""
        return (
            self.active_slots
            + len(self._pending)
            + len(self.scheduler)
            + len(self._handoff_in)
        )

    def step(self) -> list[Completion]:
        """One scheduling round: sweep deadline/cancel state over the
        active slots (host bookkeeping at the OBSERVED chain boundary —
        the ONLY place in-flight requests are interrupted), advance any
        chunked prefills by one chunk, refill free slots from the queue
        (one prefill launch each), DISPATCH one decode chain over all
        slots, then fetch the oldest in-flight chain and hand out its
        tokens. At ``pipeline_depth=1`` the dispatched chain IS the
        fetched chain — today's serial loop, op for op; at depth 2 the
        fetch trails dispatch by one chain, so the ~100 ms host
        roundtrip overlaps device execution and host bookkeeping runs
        one chain behind the device. Returns the requests that finished
        this round (possibly mid-chain — surplus chain tokens for a
        finished slot are discarded, exactly like ``generate()``
        truncating at ``max_new_tokens``)."""
        with annotate("step", chain=self.n_chains):
            if self._sentry is None:
                return self._step_impl()
            # one sentry accounting round per scheduling round: every
            # fetch inside must arrive through _sentry_fetch or
            # end_round() flags it — the production twin of the test
            # monkeypatch spies
            self._sentry.begin_round(f"step:{self.n_chains}")
            try:
                return self._step_impl()
            finally:
                self._sentry.end_round()

    def _step_impl(self) -> list[Completion]:
        if self._adapters and self._bank.version != self._merged_version:
            # register/evict moved the bank since the last merge: pick
            # the new factors up BEFORE refilling, so freshly admitted
            # tenants never decode against a stale merge (in-flight
            # slots see the new factors too — register into a free row
            # before serving it and this is a non-event for them)
            self.refresh_adapters()
        with annotate("sweep"):
            done: list[Completion] = list(self._sweep())
            if self._flight is not None and done:
                self._flight.sweep(len(done))
            done.extend(self._advance_pending())
            if self._slo:
                # preemption decision at the chain boundary, BEFORE
                # refill: a freed (swapped-out) slot is refillable this
                # very round, so the waiting high-class request starts
                # immediately
                done.extend(self._maybe_preempt())
        for s in range(self.n_slots):
            if self._slots[s] is not None or s in self._pending:
                continue
            req = self._pop_request()
            if req is None:
                break
            if self._flight is not None:
                self._flight.request_popped(req.request_id)
            with annotate("refill", rid=req.request_id, slot=s):
                done.extend(self._refill(s, req))
        occupancy = self.active_slots
        if occupancy:
            chain_id = self.n_chains
            if self._flight is not None:
                # occupancy at dispatch = chain utilization sample
                self._flight.chain_start(
                    occupancy, self.n_slots, chain=chain_id
                )
            if self._chaos is not None:
                chaos_lib.maybe_stall(
                    self._chaos, self.n_chains, flight=self._flight
                )
            if self._inject_logits:
                # global decode-step base for the deterministic injector
                # — a traced scalar, so faulty and clean chains are the
                # same compiled program
                args = (self.params, self._state, jnp.asarray(
                    self.n_chains * self.tokens_per_launch, jnp.int32
                ))
            else:
                args = (self.params, self._state)
            if self._sentry is not None:
                # re-upload probe: a host-numpy leaf in the dispatch
                # tree re-uploads H2D every chain (the
                # device_materialize trap) — isinstance walk, no fetch
                self._sentry.check_args(args, label="decode_chain")
            # async dispatch: self._state becomes the chain's OUTPUT
            # futures. Later parks/prefills/chains consume them without
            # a host sync — device program order runs them after this
            # chain — so the fetch below is the only place the host
            # waits.
            with annotate(
                "chain_dispatch", chain=chain_id, occupancy=occupancy,
                **self._chain_rows(),
            ):
                self._state, out = self._chain(*args)
            self.n_chains += 1
            if self._spec:
                self.n_verify_forwards += self.tokens_per_launch
            self._inflight.append(
                _InFlight(out, list(self._slots), chain_id)
            )
        # fetch the oldest chain(s). While slots are active, keep
        # depth-1 chains in flight (depth 1: fetch what was just
        # dispatched — serial); once the observed stream is empty, drain
        # fully (trailing chains carry only junk-decode of parked or
        # naturally-exhausted slots, dropped by the view identity check).
        target = self._depth - 1 if self.active_slots else 0
        while len(self._inflight) > target:
            done.extend(self._collect_chain())
        return done

    def _chain_rows(self) -> dict:
        """The rows of K and V that one decode attention call a step of
        the chain about to be dispatched reads, summed over its
        ``tokens_per_launch`` (T) steps and the live slots: the fields of
        ``prog:chain_dispatch`` that ``decode_attention_roofline.*``
        charges the traced calls by (a call's shapes hold neither the
        depths nor which slots are live).

        A slot of depth ``d`` (the positions its cache holds) and budget
        ``r`` lives ``n = min(T, r)`` steps; step ``j`` writes position
        ``d + j`` and attends ``d + j + 1`` rows: ``kv_rows`` sums
        ``n (d + 1) + n (n - 1) / 2`` (a full-length cache; a latent
        cache's rows alike), ``ring_rows`` ``min(d + j + 1, R)`` where
        the cache holds a ring of ``R`` rows. Host state alone, no fetch:
        the ``_Active``s, advanced by the chains still in flight, which
        the device runs past any EOS the host has not fetched yet. Paged
        and speculative chains read through other kernels by other
        counts: no field."""
        if self._paged or self._spec:
            return {}
        t, ring, inflight = self.tokens_per_launch, self._ring, self._inflight
        kv = rows = 0
        for s, act in enumerate(self._slots):
            if act is None:
                continue
            a = len(act.request.prompt) + len(act.tokens)  # d + 1
            n = act.remaining
            if inflight:
                behind = sum(fl.view[s] is act for fl in inflight)
                ahead = min(n, t * behind)
                a, n = a + ahead, n - ahead
            if n > t:  # comparisons, not min(): this runs every dispatch
                n = t
            if n <= 0:
                continue
            kv += n * (2 * a + n - 1) // 2
            if ring:
                u = ring - a + 1 if a <= ring else 0  # steps inside the ring
                if u > n:
                    u = n
                rows += u * (2 * a + u - 1) // 2 + (n - u) * ring
        return {"kv_rows": kv, "ring_rows": rows} if ring else {"kv_rows": kv}

    def _sentry_fetch(self, x):
        """The budgeted host fetch: every budgeted call site
        (``_collect_chain`` / ``_refill`` / ``_refill_paged`` /
        ``_advance_one`` / ``_accept_refill`` / ``_swap_out``) fetches
        through here so
        the contract sentry (ISSUE 19) can attribute it — a bare
        ``jax.device_get`` anywhere else in the request loop is exactly
        what the sentry's round accounting flags at runtime (and the
        graftcheck ``fetch-budget`` rule flags statically; this wrapper
        is the rule's measuring-instrument exemption, like
        ``serve/__main__.py``). Sentry-off it IS ``jax.device_get`` —
        one extra host-side call frame, nothing else."""
        if self._sentry is not None:
            self._sentry.budgeted_fetch()
        return jax.device_get(x)

    def _collect_chain(self) -> list[Completion]:
        """Fetch the OLDEST in-flight chain (ONE batched ``device_get``
        — the chain's budgeted fetch) and hand its tokens to the slot
        views snapshotted at its dispatch. A slot that completed or was
        refilled inside the pipeline window fails the snapshot identity
        check in the distribute and ignores this chain's junk rows."""
        fl = self._inflight.popleft()
        with annotate("chain_fetch", chain=fl.chain_id):
            # the chain's ONE host fetch
            fetched = self._sentry_fetch(fl.out)
        gen_before = self.generated_tokens
        with annotate("distribute", chain=fl.chain_id) as span:
            if self._spec:
                if self._guard:
                    toks, counts, oks = fetched
                else:
                    (toks, counts), oks = fetched, None
                done = self._distribute_spec(
                    toks, counts, oks, view=fl.view
                )
            else:
                if self._guard:
                    toks, oks = fetched
                else:
                    toks, oks = fetched, None
                done = self._distribute(toks, oks, view=fl.view)
            span.set_metadata(tokens=self.generated_tokens - gen_before)
        if self._flight is not None:
            self._flight.chain_end(
                tokens=self.generated_tokens - gen_before,
                occupancy=self.active_slots,
                chain=fl.chain_id,
            )
        return done

    def _pop_request(self) -> Request | None:
        """Queue pop, chunk-aware when chunked prefill is on: with a
        long prompt already mid-chunked-prefill, only requests that fit
        one chunk pop (they slip around the long one into free slots
        instead of queueing a second multi-step prefill behind it).

        Paged engines (ISSUE 13) additionally pass a ``fits`` predicate
        — enough FREE pages for the request's whole prompt + budget
        (conservative: prefix sharing can only reduce the real need) — so
        oversubscribed slot counts degrade to queueing, never to a
        mid-decode allocation failure. When nothing fits but the queue
        is non-empty, cold unpinned prefix segments are evicted one at a
        time (each eviction returns pages to the pool) and the pop
        retried; the loop is bounded by the segment count."""
        fits = None
        if self._paged:
            pool = self._pool

            def fits(r):
                return pool.available >= pool.pages_needed(
                    len(r.prompt) + r.max_new_tokens
                )

        with annotate("queue_pop") as span:
            while True:
                if self._chunk:
                    req = self.scheduler.pop(
                        chunk=self._chunk,
                        pending_long=len(self._pending), fits=fits,
                    )
                else:
                    req = self.scheduler.pop(fits=fits)
                if req is not None or fits is None:
                    break
                if (
                    len(self.scheduler) == 0
                    or self.prefix is None
                    or not self.prefix.evict_coldest()
                ):
                    break
            span.set_metadata(rid=-1 if req is None else req.request_id)
        return req

    def _deadline_for(self, req: Request) -> float | None:
        return (
            req.deadline_s if req.deadline_s is not None
            else self._deadline
        )

    def _sweep(self) -> list[Completion]:
        """Chain-boundary enforcement of host-side lifecycle state:
        complete active slots whose request was cancelled or whose
        deadline expired. Pure host bookkeeping + the park launch —
        never a device fetch, never a mid-chain interrupt (tokens a
        request earned before the boundary are kept)."""
        done: list[Completion] = []
        if not self._cancelled and self._deadline is None and not any(
            a is not None and a.request.deadline_s is not None
            for a in self._slots
        ):
            return done
        now = time.perf_counter()
        for s, act in enumerate(self._slots):
            if act is None:
                continue
            req = act.request
            reason = None
            if req.request_id in self._cancelled:
                reason = "cancelled"
                self._cancelled.discard(req.request_id)
                self.n_cancelled += 1
            else:
                dl = self._deadline_for(req)
                if dl is not None and now - req.submitted_s > dl:
                    reason = "deadline"
                    self.n_deadline_expired += 1
                    if self._flight is not None:
                        self._flight.fault(
                            "deadline", rid=req.request_id, slot=s
                        )
            if reason is not None:
                self._slots[s] = None
                if self._paged:
                    self._park_paged(s, act)
                elif act.remaining > 0:
                    self._state["remaining"] = self._park(
                        self._state["remaining"], s
                    )
                done.append(self._complete(act, reason))
        return done

    def _maybe_preempt(self) -> list[Completion]:
        """SLO preemption decision (ISSUE 20), at the chain boundary
        only. Pressure = a strictly higher class is waiting AND no slot
        can take it (every slot occupied/pending, or — paged — the pool
        cannot back the best waiter even with a free slot). Under
        pressure the lowest-tier active slot (:func:`..serve.slo.
        choose_victim` — strictly-lower tier only, most recent admit
        loses first) is swapped out. Before the swap the in-flight
        pipeline is DRAINED: the device is ahead of the host's token
        view at depth > 1, and the swap must capture exactly the state
        the host has accounted for — those collections are the chains'
        own already-budgeted fetches, so the budget stays chains +
        prefills + splices + swaps. After draining, the victim is
        re-checked (it may have completed inside a drained chain). The
        chaos ``preempt_at_chain`` injector forces a named slot through
        the same path, once, for pressure-free testing."""
        done: list[Completion] = []
        victim: int | None = None
        c = self._chaos
        if (
            c is not None
            and getattr(c, "preempts", False)
            and not self._chaos_preempt_fired
            and self.n_chains >= c.preempt_at_chain
        ):
            self._chaos_preempt_fired = True
            victim = int(c.preempt_slot)
            if (
                victim >= self.n_slots
                or self._slots[victim] is None
            ):
                return done
        else:
            wait = self.scheduler.peek_priority()
            if wait is None:
                return done
            free = any(
                self._slots[s] is None and s not in self._pending
                for s in range(self.n_slots)
            )
            pressure = not free
            if not pressure and self._paged:
                head = self.scheduler.peek_request()
                if head is not None and int(getattr(
                    head, "priority", 0
                )) == wait:
                    need = self._pool.pages_needed(
                        len(head.prompt) + head.max_new_tokens
                    )
                    pressure = self._pool.available < need
            if not pressure:
                return done
            victim = choose_victim(
                [
                    (s, int(getattr(a.request, "priority", 0)),
                     a.request.request_id)
                    for s, a in enumerate(self._slots)
                    if a is not None
                ],
                wait,
            )
            if victim is None:
                return done
        # drain the pipeline so device state == the host's token view
        # (each collection is that chain's own budgeted fetch)
        while self._inflight:
            done.extend(self._collect_chain())
        if self._slots[victim] is None:
            # the victim finished inside a drained chain — pressure is
            # already relieved by its free slot
            return done
        self._swap_out(victim)
        return done

    def _swap_out(self, slot: int) -> None:
        """Park slot ``slot``'s request to host: ONE budgeted batched
        ``device_get`` (segment + sampling leaves — the swap fetch the
        budget line counts), then the slot parks exactly like a
        completion would (pages return to the pool on paged engines)
        and the request re-enters the queue at its ARRIVAL position
        (``PriorityScheduler.requeue``) holding a
        :class:`..serve.slo.SwapRecord` for the swap-in."""
        act = self._slots[slot]
        req = act.request
        position = len(req.prompt) + len(act.tokens) - 1
        seg_len = bucket_len(position, self.window)
        if self._paged:
            row = jnp.asarray(
                act.pages
                + [self._pool_pages] * (
                    self._pages_per_slot - len(act.pages)
                ),
                jnp.int32,
            )
            out = self._swap_out_jit(
                self._state, row, slot, position, seg_len=seg_len
            )
        else:
            out = self._swap_out_jit(self._state, slot, seg_len=seg_len)
        host = self._sentry_fetch(out)  # the swap's ONE budgeted fetch
        self.n_swaps_out += 1
        self._slots[slot] = None
        if self._paged:
            self._park_paged(slot, act)
        else:
            self._state["remaining"] = self._park(
                self._state["remaining"], slot
            )
        if act.segment is not None:
            # the slot no longer decodes from its splice donor; swap-in
            # re-splices from the parked segment, not the donor
            self.prefix.release(act.segment)
            act.segment = None
        self._swapped[req.request_id] = SwapRecord(
            active=act,
            segment=host["segment"],
            last_tok=host["last_tok"],
            key=host["key"],
            position=position,
            seg_len=seg_len,
            hist=host.get("hist"),
            hist_len=host.get("hist_len"),
            preempt_t=time.perf_counter(),
        )
        self.scheduler.requeue(req)
        if self._flight is not None:
            self._flight.preempted(
                req.request_id, slot=slot, position=position,
                tokens=len(act.tokens),
            )

    def _swap_in(self, slot: int, req: Request,
                 rec: SwapRecord) -> list[Completion]:
        """Resume a preempted request into slot ``slot``: re-upload the
        parked leaves and replay the accept splice with the request's
        LIVE progress (``remaining``/``key``/history verbatim) — zero
        host fetches, so the budget line grows only by swap-OUTS. A
        failure isolates to this request (``"error"``, pre-preemption
        tokens kept), exactly like a raising prefill."""
        act = rec.active
        pages: list[int] = []
        try:
            segment = jax.tree_util.tree_map(jnp.asarray, rec.segment)
            kw = {}
            if self._spec:
                kw["hist"] = jnp.asarray(rec.hist)
                kw["hist_len"] = jnp.asarray(rec.hist_len)
            if self._adapters:
                kw["aid"] = int(getattr(req, "adapter", 0))
            if self._paged:
                pages = self._pool.alloc(self._pool.pages_needed(
                    len(req.prompt) + req.max_new_tokens
                ))
                row = jnp.asarray(
                    pages
                    + [self._pool_pages] * (
                        self._pages_per_slot - len(pages)
                    ),
                    jnp.int32,
                )
                self._state = self._swap_in_jit(
                    self.params, self._state, segment, row,
                    jnp.asarray(rec.last_tok), jnp.asarray(rec.key),
                    rec.position, slot, act.remaining, **kw,
                )
                act.pages = pages
            else:
                self._state = self._swap_in_jit(
                    self.params, self._state, segment,
                    jnp.asarray(rec.last_tok), jnp.asarray(rec.key),
                    rec.position, slot, act.remaining, **kw,
                )
        except Exception:
            if pages:
                self._pool.release_all(pages)
            self.n_prefill_errors += 1
            if self._flight is not None:
                self._flight.fault(
                    "swap_in_error", rid=req.request_id, slot=slot
                )
            return [self._complete(act, "error")]
        self.n_swaps_in += 1
        self._slots[slot] = act
        if self._flight is not None:
            self._flight.resumed(
                req.request_id, slot=slot,
                wait_s=time.perf_counter() - rec.preempt_t,
            )
        return []

    def run_until_idle(self, max_steps: int = 10_000) -> list[Completion]:
        """Drain queue + slots; returns completions in finish order."""
        out: list[Completion] = []
        for _ in range(max_steps):
            if self.idle:
                return out
            out.extend(self.step())
        raise RuntimeError(f"not idle after {max_steps} steps")

    def cancel(self, request_id: int) -> bool:
        """Host-side cancellation. Returns True when ``request_id`` is
        known (queued or decoding) — it will complete with
        ``finish_reason == "cancelled"`` at the next chain/refill
        boundary (queued: zero device work; decoding: tokens earned so
        far are kept, the slot parks). False for ids already finished or
        never submitted. Never interrupts a running chain and never
        costs a device fetch — cancellation is pure bookkeeping the
        boundary sweep enforces."""
        known = any(
            a is not None and a.request.request_id == request_id
            for a in self._slots
        ) or any(
            p.request.request_id == request_id
            for p in self._pending.values()
        ) or self.scheduler.has(request_id)
        if known:
            self._cancelled.add(request_id)
        return known

    @property
    def closed(self) -> bool:
        return self.scheduler.closed

    def close(self) -> None:
        """Stop admitting requests: every later :meth:`submit` raises
        :class:`..serve.scheduler.QueueClosed` (synchronous
        backpressure, like ``QueueFull``). Work already accepted —
        queued or decoding — is unaffected; pair with :meth:`drain` for
        a graceful shutdown. Idempotent."""
        self.scheduler.close()

    def drain(self, max_steps: int = 10_000) -> list[Completion]:
        """Graceful shutdown: :meth:`close` the queue, then run every
        accepted request to completion and return the completions in
        finish order. The engine stays usable for inspection (stats,
        counters) afterwards; it just admits nothing new."""
        self.close()
        return self.run_until_idle(max_steps)

    def _refill(self, slot: int, req: Request) -> list[Completion]:
        """Prefill ``req`` into ``slot``. One launch + one scalar fetch
        (the first sampled token — needed host-side for EOS/max_new==1
        admission into the decode phase).

        With the prefix cache on, a longest-prefix-match against the
        radix index turns the full prefill into a segment splice + a
        prefill over only the uncached suffix (:meth:`_splice_fn`) —
        still one launch + one scalar fetch. Either way the prompt's own
        prefix is inserted into the index (when not already resident),
        and a hit pins its donor segment until this request completes,
        so eviction only ever happens here, BETWEEN decode chains, and
        never under a slot mid-decode.

        Prefix keys are NAMESPACED by the request's (adapter id,
        tenant-generation) pair (:meth:`_prefix_key`): a tenant's K/V
        depends on its factors, so a cross-tenant splice would seed a
        slot with wrong-adapter prefixes — disjoint key ranges make that
        lookup structurally impossible while keeping the index itself
        adapter-oblivious, and the generation keeps it impossible when a
        later tenant recycles an evicted tenant's row.

        The same staleness check guards the request itself: if its
        tenant was evicted (or the row re-registered) since submit, the
        request completes here as ``"adapter_evicted"`` — zero device
        work, zero fetches — rather than decode under zeroed or, worse,
        another tenant's factors. Cancelled or deadline-expired requests
        complete here the same zero-work way (``"cancelled"`` /
        ``"deadline"`` — refill is the queue's boundary, the sweep is
        the active slots'). A prefill that RAISES is isolated to its
        request: the slot parks, the request completes ``"error"``, and
        the engine keeps serving everyone else — one poisoned prompt
        (or one injected :class:`..utils.chaos.ChaosError`) must never
        take the process down.

        A request carrying a :class:`..serve.slo.SwapRecord` (it was
        PREEMPTED while decoding — ISSUE 20) resumes through
        :meth:`_swap_in` instead of prefilling; if it was cancelled or
        expired while parked, it completes with the tokens it earned
        BEFORE the preemption (a preempted request is started work, not
        unstarted)."""
        rec = (
            self._swapped.pop(req.request_id, None)
            if self._slo else None
        )
        if req.request_id in self._cancelled:
            self._cancelled.discard(req.request_id)
            self.n_cancelled += 1
            return [self._bounce(req, rec, "cancelled")]
        dl = self._deadline_for(req)
        if dl is not None and time.perf_counter() - req.submitted_s > dl:
            self.n_deadline_expired += 1
            if self._flight is not None:
                self._flight.fault("deadline", rid=req.request_id)
            return [self._bounce(req, rec, "deadline")]
        aid = int(getattr(req, "adapter", 0))
        if aid and not (
            self._bank.registry.is_live(aid)
            and self._bank.generation(aid) == req.adapter_gen
        ):
            self.adapter_rejected += 1
            if self._flight is not None:
                self._flight.fault(
                    "adapter_evicted", rid=req.request_id, adapter=aid
                )
            return [self._bounce(req, rec, "adapter_evicted")]
        if aid:
            self.adapter_requests += 1
        if rec is not None:
            return self._swap_in(slot, req, rec)
        if self._role == "decode":
            # disaggregated refill (ISSUE 18): the prefill already ran
            # on another engine — splice its transferred segment in
            return self._accept_refill(slot, req)
        prompt = [int(t) for t in req.prompt]
        p_len = len(prompt)
        bucket = bucket_len(p_len, self.window)
        pkey = self._prefix_key(prompt, aid)
        hit = (
            self.prefix.lookup(pkey, self._min_hit_depth)
            if self.prefix is not None
            else None
        )
        grow = self.prefix is not None and tuple(pkey) not in self.prefix
        if self._chunk and (
            p_len - (hit[0] if hit is not None else 0) > self._chunk
        ):
            # chunked prefill: the uncached length exceeds the per-step
            # quantum — stream it in chunks instead of stalling every
            # co-scheduled slot for the whole prompt
            return self._begin_chunked(
                slot, req, prompt, p_len, pkey, hit, grow, aid
            )
        if self._role == "prefill":
            return self._refill_handoff(
                slot, req, prompt, p_len, bucket, pkey, hit, grow, aid
            )
        if self._paged:
            return self._refill_paged(
                slot, req, prompt, p_len, bucket, pkey, hit, grow, aid
            )
        segment = None
        try:
            if self._chaos is not None:
                chaos_lib.maybe_fail_prefill(self._chaos, req.request_id)
            if hit is not None:
                depth, segment = hit
                # pin the donor FIRST: in the except path below,
                # ``segment is not None`` then always means "acquired"
                self.prefix.acquire(segment)
                suffix = prompt[depth:]
                s_bucket = bucket_len(len(suffix), self.window)
                tokens = jnp.asarray(
                    [suffix + [0] * (s_bucket - len(suffix))], jnp.int32
                )
                full = (
                    jnp.asarray(
                        [prompt + [0] * (bucket - p_len)], jnp.int32
                    )
                    if self._spec
                    else tokens  # dead operand when speculation is off
                )
                # aid rides as a keyword ONLY when adapters are on: the
                # off engine's call signature (and so its jaxpr) stays
                # identical
                akw = {"aid": aid} if self._adapters else {}
                self._state, first, new_seg = self._splice(
                    self.params, self._state, segment.handle, tokens,
                    full, depth, p_len, slot, req.seed,
                    req.max_new_tokens, seg_len=bucket, grow=grow, **akw,
                )
                self.n_splices += 1
                self.prefix_hit_tokens += depth
            else:
                padded = prompt + [0] * (bucket - p_len)
                tokens = jnp.asarray([padded], jnp.int32)
                akw = {"aid": aid} if self._adapters else {}
                self._state, first, new_seg = self._prefill(
                    self.params, self._state, tokens, p_len, slot,
                    req.seed, req.max_new_tokens, **akw,
                )
                self.n_prefills += 1
            if grow:
                self.prefix.insert(
                    tuple(pkey), new_seg, self._nbytes(new_seg)
                )
            with annotate(
                "prefill_fetch", rid=req.request_id,
                bucket=bucket if segment is None else s_bucket,
            ):
                first = int(self._sentry_fetch(first))
        except Exception:
            # request-level isolation: unpin any splice donor, park the
            # slot (prefill may have set its device-side budget before
            # raising — the park makes later chains treat it as
            # inactive; refill rewrites the whole slot anyway) and keep
            # serving. The fault is reported through the completion.
            if segment is not None:
                self.prefix.release(segment)
            self.n_prefill_errors += 1
            if self._flight is not None:
                self._flight.fault(
                    "prefill_error", rid=req.request_id, slot=slot
                )
            self._state["remaining"] = self._park(
                self._state["remaining"], slot
            )
            return [self._complete_unstarted(req, "error")]
        return self._activate(
            slot, req, first, segment,
            hit[0] if segment is not None else 0,
        )

    def _refill_paged(self, slot: int, req: Request, prompt: list[int],
                      p_len: int, bucket: int, pkey: list[int], hit,
                      grow: bool, aid: int) -> list[Completion]:
        """Paged twin of :meth:`_refill`'s device leg. The host side owns
        all page arithmetic — which donor pages are shared in place,
        which one boundary page copy-on-writes, which fresh pages the
        pool hands out — and ships it to the device as one traced row
        vector plus a CoW id pair; the device programs never recompile
        on geometry. ``_pop_request``'s ``fits`` predicate guaranteed
        the fresh allocation below succeeds (conservatively — sharing
        only reduces the need), so ``PoolExhausted`` here would be a
        bookkeeping bug, caught by the same isolation path as a raising
        prefill."""
        pool = self._pool
        ps = self._page_size
        sentinel = self._pool_pages
        n_alloc = pool.pages_needed(p_len + req.max_new_tokens)
        segment = None
        pages: list[int] = []
        try:
            if self._chaos is not None:
                chaos_lib.maybe_fail_prefill(self._chaos, req.request_id)
            akw = {"aid": aid} if self._adapters else {}
            if hit is not None:
                depth, segment = hit
                # pin the donor FIRST, same contract as the classic path
                self.prefix.acquire(segment)
                shared_full = depth // ps
                boundary = depth % ps != 0
                # shared pages are refcounted BEFORE the fresh alloc so
                # the except path below can release `pages` uniformly
                for pid in segment.handle[:shared_full]:
                    pool.retain(pid)
                pages = list(segment.handle[:shared_full])
                pages = pages + pool.alloc(n_alloc - shared_full)
                # a partially-shared boundary page copy-on-writes into
                # the first fresh page; page-aligned depth passes the
                # sentinel pair (the compiled gather/scatter no-ops)
                cow_src = (
                    int(segment.handle[shared_full]) if boundary
                    else sentinel
                )
                cow_dst = pages[shared_full] if boundary else sentinel
                if boundary and self._flight is not None:
                    self._flight.record(
                        "page_cow", rid=req.request_id, slot=slot,
                        src=cow_src, dst=cow_dst, depth=depth,
                    )
                row = jnp.asarray(
                    pages + [sentinel] * (self._pages_per_slot - n_alloc),
                    jnp.int32,
                )
                suffix = prompt[depth:]
                s_bucket = bucket_len(len(suffix), self.window)
                tokens = jnp.asarray(
                    [suffix + [0] * (s_bucket - len(suffix))], jnp.int32
                )
                full = (
                    jnp.asarray(
                        [prompt + [0] * (bucket - p_len)], jnp.int32
                    )
                    if self._spec
                    else tokens  # dead operand when speculation is off
                )
                self._state, first = self._splice_paged(
                    self.params, self._state, row, tokens, full, depth,
                    p_len, slot, req.seed, req.max_new_tokens,
                    cow_src, cow_dst, **akw,
                )
                self.n_splices += 1
                self.prefix_hit_tokens += depth
            else:
                pages = pool.alloc(n_alloc)
                row = jnp.asarray(
                    pages + [sentinel] * (self._pages_per_slot - n_alloc),
                    jnp.int32,
                )
                padded = prompt + [0] * (bucket - p_len)
                tokens = jnp.asarray([padded], jnp.int32)
                self._state, first = self._prefill_paged(
                    self.params, self._state, tokens, row, p_len, slot,
                    req.seed, req.max_new_tokens, **akw,
                )
                self.n_prefills += 1
            if grow:
                self._insert_paged_segment(pkey, pages, p_len)
            with annotate(
                "prefill_fetch", rid=req.request_id,
                bucket=bucket if segment is None else s_bucket,
            ):
                first = int(self._sentry_fetch(first))
        except Exception:
            if segment is not None:
                self.prefix.release(segment)
            if pages:
                pool.release_all(pages)
            self.n_prefill_errors += 1
            if self._flight is not None:
                self._flight.fault(
                    "prefill_error", rid=req.request_id, slot=slot
                )
            # paged park: sentinel the table too — the failed prefill
            # may have scattered into pages just released above
            self._state = self._paged_park(self._state, slot)
            return [self._complete_unstarted(req, "error")]
        return self._activate(
            slot, req, first, segment,
            hit[0] if segment is not None else 0, pages=pages,
        )

    def _refill_handoff(self, slot: int, req: Request,
                        prompt: list[int], p_len: int, bucket: int,
                        pkey: list[int], hit, grow: bool,
                        aid: int) -> list[Completion]:
        """Prefill-role refill: run the prompt's prefill (or prefix
        splice) and EMIT the finished segment as a
        :class:`..serve.scheduler.Handoff` instead of occupying a slot.
        Pure async dispatch — segment, first token and PRNG key stay
        device futures, so a prefill-role engine performs ZERO fetches.
        The request completes immediately with ``finish_reason ==
        "handoff"`` (zero tokens here; the decode side reports them).
        Prefix-index growth is unchanged: the outgoing segment doubles
        as the insert candidate, so multi-turn streams deepen the
        prefill side's index exactly as a monolithic engine's. A
        raising prefill is isolated to its request (``"error"``, donor
        unpinned, nothing was written to slot state so no park is
        needed)."""
        segment = None
        try:
            if self._chaos is not None:
                chaos_lib.maybe_fail_prefill(self._chaos, req.request_id)
            akw = {"aid": aid} if self._adapters else {}
            if hit is not None:
                depth, segment = hit
                # pin the donor FIRST, same contract as _refill
                self.prefix.acquire(segment)
                suffix = prompt[depth:]
                s_bucket = bucket_len(len(suffix), self.window)
                tokens = jnp.asarray(
                    [suffix + [0] * (s_bucket - len(suffix))], jnp.int32
                )
                seg, first, key = self._handoff_splice(
                    self.params, segment.handle, tokens, depth, p_len,
                    req.seed, seg_len=bucket, **akw,
                )
                self.n_splices += 1
                self.prefix_hit_tokens += depth
                # the splice is dispatched; its computation holds its
                # own references, so the donor unpins at the SAME
                # boundary a monolithic engine's completion would
                self.prefix.release(segment)
                segment = None
            else:
                padded = prompt + [0] * (bucket - p_len)
                tokens = jnp.asarray([padded], jnp.int32)
                seg, first, key = self._handoff_prefill(
                    self.params, tokens, p_len, req.seed, **akw,
                )
                self.n_prefills += 1
            if grow:
                self.prefix.insert(tuple(pkey), seg, self._nbytes(seg))
        except Exception:
            if segment is not None:
                self.prefix.release(segment)
            self.n_prefill_errors += 1
            if self._flight is not None:
                self._flight.fault(
                    "prefill_error", rid=req.request_id, slot=slot
                )
            return [self._complete_unstarted(req, "error")]
        return self._emit_handoff(req, seg, first, key, p_len, bucket)

    def _emit_handoff(self, req: Request, seg, first, key, p_len: int,
                      bucket: int) -> list[Completion]:
        """Park a finished prefill in the outgoing handoff map and
        complete the request ``"handoff"`` — the router (or any
        caller) collects the record via :meth:`take_handoff`. Host
        bookkeeping only; every field stays a device future."""
        self._handoffs[req.request_id] = Handoff(
            segment=seg, first=first, key=key, p_len=p_len,
            bucket=bucket, aid=int(getattr(req, "adapter", 0)),
            submitted_s=req.submitted_s,
        )
        self.n_handoffs_out += 1
        if self._flight is not None:
            self._flight.record(
                "handoff_emit", rid=req.request_id, p_len=p_len
            )
        return [self._complete_unstarted(req, "handoff")]

    def _accept_refill(self, slot: int, req: Request) -> list[Completion]:
        """Decode-role refill: splice the request's transferred segment
        into ``slot`` (:meth:`_accept_fn` / :meth:`_accept_paged_fn`)
        and fetch the handoff's first token — THE one budgeted scalar
        fetch of the disaggregated path (graftcheck ``fetch-budget``
        names this function; the prefill side fetched nothing). The
        decode-role budget is therefore chains + handoffs, and the
        fleet budget stays the sum of per-role budgets. A failing
        accept is isolated exactly like a raising prefill: pages
        released, slot parked, ``"error"`` completion, the engine
        keeps serving."""
        h = self._handoff_in.pop(req.request_id)
        pages: list[int] = []
        p_len = h.p_len
        try:
            if self._chaos is not None:
                chaos_lib.maybe_fail_prefill(self._chaos, req.request_id)
            akw = {"aid": h.aid} if self._adapters else {}
            prompt = [int(t) for t in req.prompt]
            # bucket-padded prompt seeds the n-gram history (dead
            # operand when speculation is off, like _splice_fn's)
            full = jnp.asarray(
                [prompt + [0] * (h.bucket - p_len)], jnp.int32
            )
            if self._paged:
                n_alloc = self._pool.pages_needed(
                    p_len + req.max_new_tokens
                )
                pages = self._pool.alloc(n_alloc)
                row = jnp.asarray(
                    pages + [self._pool_pages]
                    * (self._pages_per_slot - n_alloc),
                    jnp.int32,
                )
                self._state, first = self._accept_jit(
                    self.params, self._state, h.segment, full, row,
                    h.first, h.key, p_len, slot, req.max_new_tokens,
                    **akw,
                )
            else:
                self._state, first = self._accept_jit(
                    self.params, self._state, h.segment, full,
                    h.first, h.key, p_len, slot, req.max_new_tokens,
                    **akw,
                )
            self.n_handoffs_in += 1
            with annotate("prefill_fetch", rid=req.request_id):
                # the handoff's ONE fetch
                first = int(self._sentry_fetch(first))
        except Exception:
            if pages:
                self._pool.release_all(pages)
            self.n_prefill_errors += 1
            if self._flight is not None:
                self._flight.fault(
                    "prefill_error", rid=req.request_id, slot=slot
                )
            if self._paged:
                self._state = self._paged_park(self._state, slot)
            else:
                self._state["remaining"] = self._park(
                    self._state["remaining"], slot
                )
            return [self._complete_unstarted(req, "error")]
        return self._activate(
            slot, req, first, None, 0, pages=pages, kind="handoff"
        )

    def _insert_paged_segment(self, pkey: list[int], pages: list[int],
                              p_len: int) -> None:
        """Insert-on-prefill, paged flavor: the retained "segment" is the
        tuple of page ids covering the prompt's positions — the pool
        pages themselves are the storage, so retention costs ZERO extra
        HBM (the classic path copies a whole bucket-length cache tree).
        Page refs are taken FIRST; a refused insert (duplicate key /
        budget full of pinned segments) releases them, so pool
        accounting is exact either way. The index prices the segment at
        page granularity (pages x page_bytes)."""
        seg_ids = tuple(pages[: self._pool.pages_needed(p_len)])
        for pid in seg_ids:
            self._pool.retain(pid)
        if not self.prefix.insert(
            tuple(pkey), seg_ids, len(seg_ids) * self._page_bytes
        ):
            self._pool.release_all(seg_ids)

    def _release_segment_pages(self, seg) -> None:
        """Prefix-index eviction hook (paged engines): a dropped segment
        returns its page references to the pool. Runs BEFORE the index
        clears ``seg.handle``; eviction only ever happens at refill /
        pop time, and pinned (refcount > 0) segments are never victims,
        so no live slot is decoding through these pages when they
        free."""
        self._pool.release_all(seg.handle)

    def _park_paged(self, slot: int, act: _Active | None = None) -> None:
        """Host half of paged parking: dispatch the sentinel-table park
        program and hand the slot's page references back to the pool.
        Safe against in-flight chains by device program order — see
        :meth:`_paged_park_fn`."""
        self._state = self._paged_park(self._state, slot)
        if act is not None and act.pages:
            self._pool.release_all(act.pages)
            act.pages = []

    def _activate(self, slot: int, req: Request, first: int, segment,
                  cached_len: int, pages=None,
                  kind: str | None = None) -> list[Completion]:
        """Admit a just-prefilled request into the decode phase — the
        shared tail of :meth:`_refill` and a chunked prefill's final
        chunk. ``segment`` pins the splice donor until completion; an
        EOS / ``max_new == 1`` first token completes immediately and
        parks the slot (its device-side counter still shows budget).
        ``pages`` (paged engines) transfers the slot's page references
        onto the active record — released whenever the slot parks.
        ``kind`` overrides the flight-event classification (the
        disaggregated accept path stamps ``"handoff"``)."""
        self.generated_tokens += 1
        act = _Active(req, first)
        if pages:
            act.pages = pages
        act.ttft_s = time.perf_counter() - req.submitted_s
        if self._flight is not None:
            # stamped after the scalar fetch: the first token exists, so
            # the span's prefill_t is an honest first-token time
            self._flight.request_prefilled(
                req.request_id, slot,
                kind=kind
                or ("splice" if segment is not None else "prefill"),
                cached_len=cached_len,
            )
        if segment is not None:
            act.segment = segment
        if req.max_new_tokens == 1 or first == req.eos_token:
            reason = "eos" if first == req.eos_token else "length"
            if self._paged:
                self._park_paged(slot, act)
            elif act.remaining > 0:
                # early EOS: the device-side counter still shows budget;
                # park the slot so later chains treat it as inactive
                self._state["remaining"] = self._park(
                    self._state["remaining"], slot
                )
            return [self._complete(act, reason)]
        self._slots[slot] = act
        return []

    def _begin_chunked(self, slot: int, req: Request, prompt: list[int],
                       p_len: int, pkey: list[int], hit, grow: bool,
                       aid: int) -> list[Completion]:
        """Start a chunked prefill (ISSUE 11 leg b): seed a batch-1 side
        cache — zeroed, or spliced from a prefix-cache hit at its
        matched depth — and register the slot as pending. Chunks advance
        one per :meth:`step` via :meth:`_advance_pending`; until the
        final chunk lands, the slot's device budget stays 0 (decode
        chains treat it as inactive) and no fetch happens, so
        co-scheduled slots keep decoding while this prompt streams in."""
        pend = _PendingPrefill(req, slot)
        pend.prompt = prompt
        pend.aid = aid
        pend.grow = grow
        pend.pkey = pkey
        try:
            if self._chaos is not None:
                chaos_lib.maybe_fail_prefill(self._chaos, req.request_id)
            if self._paged:
                # all the slot's pages are FRESH for chunked prompts
                # (the side cache re-prefills shared positions too, so
                # the final scatter owns every page it writes — sharing
                # is lost for chunked prompts, a documented trade)
                pend.pages = self._pool.alloc(
                    self._pool.pages_needed(p_len + req.max_new_tokens)
                )
            if hit is not None:
                depth, segment = hit
                # pin the donor FIRST, same contract as _refill
                self.prefix.acquire(segment)
                pend.segment = segment
                pend.depth = depth
                if self._paged:
                    # gather-copy the donor's pages into the side cache
                    n_seg = self._pool.pages_needed(depth)
                    srow = jnp.asarray(
                        list(segment.handle[:n_seg])
                        + [self._pool_pages]
                        * (self._pages_per_slot - n_seg),
                        jnp.int32,
                    )
                    pend.cache1 = self._chunk_seed_paged(
                        self._state["cache"], srow, depth
                    )
                else:
                    pend.cache1 = self._chunk_seed(segment.handle, depth)
            else:
                pend.cache1 = self._chunk_zero()
        except Exception:
            if pend.segment is not None:
                self.prefix.release(pend.segment)
            if pend.pages:
                self._pool.release_all(pend.pages)
                pend.pages = []
            self.n_prefill_errors += 1
            if self._flight is not None:
                self._flight.fault(
                    "prefill_error", rid=req.request_id, slot=slot
                )
            # no park needed: the slot was free, its device budget is 0
            return [self._complete_unstarted(req, "error")]
        pend.done = pend.depth
        self._pending[slot] = pend
        # the first chunk runs in the SAME step the slot was claimed —
        # a pending prefill never wastes its admission round
        return self._advance_one(pend)

    def _advance_pending(self) -> list[Completion]:
        """Advance every chunked prefill by ONE chunk — the per-step
        prefill quantum. Mid chunks are a single async dispatch into the
        pending request's side cache (no fetch); a final chunk splices
        into the slot and fetches the first token (the budgeted
        prefill/splice fetch). Runs BEFORE refill in :meth:`step`, so a
        prefill begun this round is not advanced twice."""
        done: list[Completion] = []
        for slot in list(self._pending):
            done.extend(self._advance_one(self._pending[slot]))
        return done

    def _advance_one(self, pend: _PendingPrefill) -> list[Completion]:
        req = pend.request
        slot = pend.slot
        # pending prefills honor the same boundary lifecycle as queued
        # requests: cancel/deadline complete them with zero tokens (the
        # side cache is dropped, the donor segment unpinned)
        if req.request_id in self._cancelled:
            self._cancelled.discard(req.request_id)
            self.n_cancelled += 1
            self._abandon_pending(pend)
            return [self._complete_unstarted(req, "cancelled")]
        dl = self._deadline_for(req)
        if dl is not None and time.perf_counter() - req.submitted_s > dl:
            self.n_deadline_expired += 1
            if self._flight is not None:
                self._flight.fault(
                    "deadline", rid=req.request_id, slot=slot
                )
            self._abandon_pending(pend)
            return [self._complete_unstarted(req, "deadline")]
        p_len = len(pend.prompt)
        rem = p_len - pend.done
        akw = {"aid": pend.aid} if self._adapters else {}
        try:
            if rem > self._chunk:
                # mid chunk: exactly prefill_chunk tokens (full chunks
                # need no padding — ONE compiled shape), async dispatch
                # only
                tokens = jnp.asarray(
                    [pend.prompt[pend.done:pend.done + self._chunk]],
                    jnp.int32,
                )
                pend.cache1 = self._chunk_step(
                    self.params, pend.cache1, tokens, **akw
                )
                pend.done += self._chunk
                self.n_chunks += 1
                if self._flight is not None:
                    self._flight.prefill_chunk(
                        req.request_id, slot, done=pend.done, total=p_len
                    )
                return []
            # final chunk: splice into the slot + fetch the first token
            # (THE budgeted prefill/splice fetch for this request)
            f_bucket = bucket_len(rem, self.window)
            suffix = pend.prompt[pend.done:]
            tokens = jnp.asarray(
                [suffix + [0] * (f_bucket - rem)], jnp.int32
            )
            bucket = bucket_len(p_len, self.window)
            if self._role == "prefill":
                # disaggregated final chunk (ISSUE 18): extract the
                # finished segment from the side cache and EMIT it —
                # no slot splice, no fetch (the decode side fetches)
                seg, first, key = self._handoff_final(
                    self.params, pend.cache1, tokens, rem - 1,
                    req.seed, seg_len=bucket, **akw,
                )
                self.n_chunks += 1
                if pend.segment is not None:
                    self.n_splices += 1
                    self.prefix_hit_tokens += pend.depth
                    self.prefix.release(pend.segment)
                    pend.segment = None
                else:
                    self.n_prefills += 1
                if pend.grow:
                    self.prefix.insert(
                        tuple(pend.pkey), seg, self._nbytes(seg)
                    )
                del self._pending[slot]
                return self._emit_handoff(
                    req, seg, first, key, p_len, bucket
                )
            full = (
                jnp.asarray(
                    [pend.prompt + [0] * (bucket - p_len)], jnp.int32
                )
                if self._spec
                else tokens  # dead operand when speculation is off
            )
            if self._paged:
                row = jnp.asarray(
                    pend.pages
                    + [self._pool_pages]
                    * (self._pages_per_slot - len(pend.pages)),
                    jnp.int32,
                )
                self._state, first = self._chunk_final_paged(
                    self.params, pend.cache1, self._state, tokens, full,
                    rem - 1, p_len, slot, req.seed, req.max_new_tokens,
                    row, **akw,
                )
            else:
                self._state, first, new_seg = self._chunk_final(
                    self.params, pend.cache1, self._state, tokens, full,
                    rem - 1, p_len, slot, req.seed, req.max_new_tokens,
                    seg_len=bucket, grow=pend.grow, **akw,
                )
            self.n_chunks += 1
            if pend.segment is not None:
                self.n_splices += 1
                self.prefix_hit_tokens += pend.depth
            else:
                self.n_prefills += 1
            if pend.grow:
                if self._paged:
                    self._insert_paged_segment(
                        pend.pkey, pend.pages, p_len
                    )
                else:
                    self.prefix.insert(
                        tuple(pend.pkey), new_seg, self._nbytes(new_seg)
                    )
            with annotate(
                "prefill_fetch", rid=req.request_id, bucket=f_bucket
            ):
                first = int(self._sentry_fetch(first))
        except Exception:
            self._abandon_pending(pend)  # also releases pend.pages
            self.n_prefill_errors += 1
            if self._flight is not None:
                self._flight.fault(
                    "prefill_error", rid=req.request_id, slot=slot
                )
            # defensive park, same as _refill: the final chunk may have
            # set the slot's device budget before raising
            if self._paged:
                self._state = self._paged_park(self._state, slot)
            else:
                self._state["remaining"] = self._park(
                    self._state["remaining"], slot
                )
            return [self._complete_unstarted(req, "error")]
        segment = pend.segment
        cached_len = pend.depth
        pages = pend.pages
        pend.pages = []  # ownership moves to the active record
        del self._pending[slot]
        return self._activate(
            slot, req, first, segment, cached_len, pages=pages
        )

    def _abandon_pending(self, pend: _PendingPrefill) -> None:
        """Drop a pending chunked prefill: unpin its splice donor,
        return its pre-allocated pages (paged engines), and free the
        slot for the next refill. The side cache futures are simply
        released (nothing was spliced into slot state, and the slot's
        device budget — and page table — were never set, so no park is
        needed)."""
        if pend.segment is not None:
            self.prefix.release(pend.segment)
            pend.segment = None
        if pend.pages:
            self._pool.release_all(pend.pages)
            pend.pages = []
        self._pending.pop(pend.slot, None)

    def _prefix_key(self, prompt: list[int], aid: int) -> list[int]:
        """Tenant-scoped prefix-index key: shift every token by
        ``(generation * n_adapters + aid) * vocab_size`` so each tenant
        INCARNATION occupies a disjoint key range — same LPM depth
        within a tenant, zero matches across tenants. The generation
        matters because rows recycle: evict A, register B, and B lands
        on A's row — a bare-aid namespace would hand B LPM hits whose
        segments hold KV computed with A's factors. Segments keyed under
        a dead generation simply stop being reachable and age out of the
        byte budget via LRU. Host-only arithmetic (the index never sees
        real token ids for aid > 0, which is fine: keys are opaque to
        it); aid 0 keys are the raw prompt (row 0 is never reassigned,
        its generation is pinned 0), so base-model streams share the
        index exactly as before the bank existed."""
        if aid == 0:
            return prompt
        ns = self._bank.generation(aid) * self._bank.n_adapters + aid
        shift = ns * int(self.model.cfg.vocab_size)
        return [t + shift for t in prompt]

    def _distribute(self, toks, oks=None, view=None) -> list[Completion]:
        """Hand one fetched (S, T) chain block out to the slots' host
        views; free every slot that finished (budget exhausted or EOS
        mid-chain) and park early-EOS slots whose device counter still
        shows budget.

        ``view`` is the slot snapshot taken when this chain was
        DISPATCHED (``None`` = the live slots, the depth-1 case where
        nothing can change in between): a slot whose ``_Active`` is no
        longer the live one — completed or refilled inside the pipeline
        window — fails the identity check and ignores this chain's junk
        rows.

        ``oks`` (guard on) is the fetched (S, T) finite-logits flag: the
        first False step for a slot means that step's token — and
        everything after it — was sampled from NaN/Inf logits. The slot
        completes ``"nonfinite"`` with only its pre-poison tokens and is
        quarantined (parked; the next refill rewrites the slot whole,
        position counter included). Other slots' rows are untouched —
        the per-slot forward is independent across the batch dim, so
        co-scheduled requests decode token-identically to a clean run."""
        done: list[Completion] = []
        for s, act in enumerate(self._slots if view is None else view):
            if act is None or act is not self._slots[s]:
                continue
            reason = None
            for t, tok_ in enumerate(toks[s, : act.remaining]):
                if oks is not None and not oks[s, t]:
                    reason = "nonfinite"
                    self.nonfinite_quarantined += 1
                    if self._flight is not None:
                        self._flight.fault(
                            "nonfinite", rid=act.request.request_id,
                            slot=s, chain_step=t,
                        )
                    break
                tok = int(tok_)
                act.tokens.append(tok)
                act.remaining -= 1
                self.generated_tokens += 1
                if tok == act.request.eos_token:
                    reason = "eos"
                    break
            if reason is None and act.remaining == 0:
                reason = "length"
            if reason is not None:
                self._slots[s] = None
                if self._paged:
                    # paged parks on EVERY completion: an inactive slot
                    # still K/V-writes at advancing positions, and a
                    # live table would route them into freed pages
                    self._park_paged(s, act)
                elif act.remaining > 0:  # finished mid-chain (EOS/poison)
                    self._state["remaining"] = self._park(
                        self._state["remaining"], s
                    )
                done.append(self._complete(act, reason))
        return done

    def _distribute_spec(self, toks, counts, oks=None,
                         view=None) -> list[Completion]:
        """Speculative twin of :meth:`_distribute`: unpack one fetched
        (S, T, k+1) block. Step t of slot s contributed ``counts[s, t]``
        real tokens — the accepted draft prefix plus the bonus/rejection
        token — and the rest of the row is padding. The host truncates at
        the request's budget exactly like ``generate()`` does (the device
        may have verified past it within the chain; those writes land in
        the slot's own window and refill rewrites the whole slot).
        ``view`` follows the :meth:`_distribute` pipeline-window identity
        contract; ``oks`` the quarantine contract at verify-step
        granularity (a poisoned verify step discards all of that step's
        emissions)."""
        done: list[Completion] = []
        for s, act in enumerate(self._slots if view is None else view):
            if act is None or act is not self._slots[s]:
                continue
            reason = None
            for t in range(counts.shape[1]):
                if oks is not None and not oks[s, t]:
                    reason = "nonfinite"
                    self.nonfinite_quarantined += 1
                    if self._flight is not None:
                        self._flight.fault(
                            "nonfinite", rid=act.request.request_id,
                            slot=s, chain_step=t,
                        )
                    break
                n = int(counts[s, t])
                if n == 0:  # slot went inactive device-side
                    break
                self.spec_steps_consumed += 1
                self.spec_drafts_accepted += n - 1
                for tok_ in toks[s, t, : min(n, act.remaining)]:
                    tok = int(tok_)
                    act.tokens.append(tok)
                    act.remaining -= 1
                    self.generated_tokens += 1
                    if tok == act.request.eos_token:
                        reason = "eos"
                        break
                if reason is not None or act.remaining == 0:
                    break
            if reason is None and act.remaining == 0:
                reason = "length"
            if reason is not None:
                self._slots[s] = None
                if self._paged:
                    self._park_paged(s, act)
                elif act.remaining > 0:  # finished mid-chain via EOS
                    self._state["remaining"] = self._park(
                        self._state["remaining"], s
                    )
                done.append(self._complete(act, reason))
        return done

    def _complete_unstarted(self, req: Request, reason: str) -> Completion:
        """A zero-token completion for a request bounced at a boundary
        before any device work (cancelled / deadline / adapter_evicted /
        prefill error): zero fetches, zero tokens, synchronous. Drops
        any accepted-but-unspliced handoff for the request (a decode
        request cancelled while queued must not strand its transfer
        record — the device futures are simply released)."""
        self._handoff_in.pop(req.request_id, None)
        with annotate("complete", rid=req.request_id, tokens=0):
            comp = Completion(
                request_id=req.request_id,
                prompt=[int(t) for t in req.prompt],
                tokens=[],
                finish_reason=reason,
                latency_s=time.perf_counter() - req.submitted_s,
            )
        if self._flight is not None:
            self._flight.request_completed(
                req.request_id, reason, tokens=0,
                latency_s=comp.latency_s,
            )
        return comp

    def _bounce(self, req: Request, rec, reason: str) -> Completion:
        """Boundary completion for a request the refill lifecycle checks
        reject: zero-work (:meth:`_complete_unstarted`) for a request
        that never started, but a PREEMPTED request (carrying a
        :class:`..serve.slo.SwapRecord`) keeps the tokens it earned
        before the swap — preemption must never silently discard
        delivered progress."""
        if rec is not None:
            return self._complete(rec.active, reason)
        return self._complete_unstarted(req, reason)

    def _complete(self, act: _Active, reason: str) -> Completion:
        if act.segment is not None:
            # the slot no longer decodes from this segment's splice;
            # unpin it (it stays resident + hot for the next hit)
            self.prefix.release(act.segment)
            act.segment = None
        with annotate(
            "complete", rid=act.request.request_id, tokens=len(act.tokens)
        ):
            comp = Completion(
                request_id=act.request.request_id,
                prompt=[int(t) for t in act.request.prompt],
                tokens=act.tokens,
                finish_reason=reason,
                latency_s=time.perf_counter() - act.request.submitted_s,
                ttft_s=act.ttft_s,
            )
        if self._flight is not None:
            # the span records the Completion's OWN numbers, so the
            # histogram percentiles are sample-identical to sorting the
            # completion list (only the bucket rounding differs)
            self._flight.request_completed(
                comp.request_id, reason, tokens=len(comp.tokens),
                latency_s=comp.latency_s, ttft_s=comp.ttft_s,
            )
        return comp

    def prefix_stats(self) -> dict[str, int | float]:
        """Prefix-cache counters for the serving receipt: index stats
        (segments / used+evicted bytes / hits / misses) plus the engine's
        splice count, reused-token total, and the resulting hit rate.
        All host bookkeeping — reading them costs no device fetch."""
        if self.prefix is None:
            return {"prefix_cache": 0}
        looked = self.prefix.hits + self.prefix.misses
        return {
            "prefix_cache": 1,
            **{f"prefix_{k}": v for k, v in self.prefix.stats().items()},
            "prefix_hit_rate": self.prefix.hits / max(1, looked),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "n_splices": self.n_splices,
        }

    def spec_stats(self) -> dict[str, int | float]:
        """Speculation counters for the serving receipt. Mean accepted
        length is per CONSUMED verify step (1.0 would mean drafting never
        helped; the mechanism receipt wants > 1); acceptance rate is the
        fraction of offered draft tokens accepted. All host bookkeeping —
        no device fetch."""
        if not self._spec:
            return {"speculative": 0}
        steps = max(1, self.spec_steps_consumed)
        return {
            "speculative": 1,
            "spec_k": self._spec_k,
            "spec_ngram": self._spec_ngram,
            "n_verify_forwards": self.n_verify_forwards,
            "spec_steps_consumed": self.spec_steps_consumed,
            "spec_drafts_accepted": self.spec_drafts_accepted,
            "spec_mean_accepted_len":
                1.0 + self.spec_drafts_accepted / steps,
            "spec_acceptance_rate":
                self.spec_drafts_accepted / (steps * self._spec_k),
        }

    def fault_stats(self) -> dict[str, int | float]:
        """Robustness counters for the serving receipt (same pattern as
        :meth:`spec_stats` — host bookkeeping, no device fetch):
        configured deadline/guard/chaos state plus how much traffic each
        failure path handled. ``chaos``/``deadline_s``/``guard_nonfinite``
        are config; the counters are OUTCOMES."""
        return {
            "deadline_s": float(self._deadline or 0.0),
            "guard_nonfinite": int(self._guard),
            "chaos": int(self._chaos is not None),
            "deadline_expired": self.n_deadline_expired,
            "cancelled": self.n_cancelled,
            "nonfinite_quarantined": self.nonfinite_quarantined,
            "prefill_errors": self.n_prefill_errors,
        }

    def refresh_adapters(self) -> None:
        """Re-merge the bank's factors into the served params after a
        :meth:`..adapters.bank.AdapterBank.register` / ``evict`` on a
        LIVE engine. The factor arrays are functionally updated, so the
        engine's merged tree must be rebuilt — shapes are unchanged, so
        nothing recompiles. :meth:`step` calls this AUTOMATICALLY when
        the bank's version moved past the engine's last merge, so a
        plain register -> submit -> step sequence serves the new factors
        with no extra call; invoke it directly only to take the re-merge
        eagerly. Requests already decoding keep their slot's id but see
        the new factors (register into a FREE row before serving it and
        this is a non-event for in-flight traffic)."""
        if not self._adapters:
            raise ValueError("engine has no adapter bank")
        merged = self._bank.merge_params(self._base_params)
        if self._shard:
            # keep the re-merged tree committed to its rule shardings —
            # an uncommitted replacement would silently recompile every
            # program against replicated params
            merged = self._strategy.shard_state(merged)
        self.params = merged
        self._merged_version = self._bank.version
        if self._flight is not None:
            self._flight.record(
                "adapter_refresh", version=self._merged_version
            )

    def adapter_stats(self) -> dict[str, int | float]:
        """Multi-tenancy counters for the serving receipt (same pattern
        as :meth:`spec_stats`): bank geometry + registry occupancy + how
        much traffic ran under a non-base adapter. All host bookkeeping —
        no device fetch."""
        if not self._adapters:
            return {"adapters": 0}
        reg = self._bank.registry
        return {
            "adapters": 1,
            "n_adapters": self._bank.n_adapters,
            "lora_rank": self._bank.rank,
            "adapters_registered": len(reg),
            "adapter_requests": self.adapter_requests,
            "adapter_rejected": self.adapter_rejected,
            "adapter_bytes": reg.used_bytes,
        }

    def flight_stats(self) -> dict[str, int | float]:
        """Flight-recorder aggregate for the serving receipt: event /
        span / dump counters + the streaming-histogram percentiles
        (``ttft_p95_s``-style keys). ``{"flight": 0}`` when the recorder
        is off. Host bookkeeping only."""
        if self._flight is None:
            return {"flight": 0}
        return self._flight.summary()

    def pipeline_stats(self) -> dict[str, int | float]:
        """Pipelining counters for the serving receipt (ISSUE 11):
        configured depth / prefill quantum plus how many prefill chunks
        ran. ``pipeline_depth`` / ``prefill_chunk`` are config;
        ``n_chunks`` is an outcome. Host bookkeeping only — no device
        fetch."""
        return {
            "pipeline_depth": self._depth,
            "prefill_chunk": self._chunk,
            "n_chunks": self.n_chunks,
        }

    def page_stats(self) -> dict[str, int | float]:
        """Paged-KV counters for the serving receipt (ISSUE 13): pool
        geometry (config: ``paged`` / ``page_size`` / ``pool_pages``)
        plus occupancy outcomes (``pages_*`` counters).
        ``hbm_high_water_bytes`` is the pool HBM high-water mark —
        ``high_water`` pages priced at the per-page leaf footprint —
        the number the oversubscription win is stated in. ``kv_bits``
        (0 = full precision) and ``paged_kernel`` are config too
        (ISSUE 17); ``page_bytes`` already prices quantized
        leaves honestly (int4's packed uint8 + bf16 scales halve it vs
        int8 exactly). Host bookkeeping only — no device fetch."""
        if not self._paged:
            return {"paged": 0}
        return {
            "paged": 1,
            "page_size": self._page_size,
            "pool_pages": self._pool_pages,
            "page_bytes": self._page_bytes,
            "kv_bits": self._kv_bits,
            "paged_kernel": int(self._paged_kernel),
            "hbm_high_water_bytes":
                self._pool.high_water * self._page_bytes,
            **{f"pages_{k}": v for k, v in self._pool.stats().items()},
        }

    def audit_decode_hlo(
        self, whitelist: tuple[str, ...] = ("all-reduce",)
    ) -> dict:
        """Compile the decode chain AOT and audit its HLO for
        collectives (ISSUE 15): a correctly head-sharded engine's chain
        contains ONLY attention/FFN all-reduces — an all-gather or a
        reshard copy means a slot leaf lost its sharding somewhere and
        the per-chip HBM claim is a lie. Returns (and caches, for
        :meth:`tp_stats`) :func:`..parallel.tensor_parallel.audit_hlo`'s
        verdict dict.

        EXPLICIT, never automatic: ``lower().compile()`` is an AOT
        compile that does NOT populate the jit dispatch cache, so
        auditing costs one extra chain compile — fine on the CPU test
        mesh or once per receipt run, not something to hide in the
        constructor of a 1.2B engine."""
        args = [self.params, self._state]
        if self._inject_logits:
            args.append(jnp.asarray(0, jnp.int32))
        hlo = self._chain.lower(*args).compile().as_text()
        self._tp_audit = audit_hlo(hlo, whitelist=whitelist)
        return self._tp_audit

    def tp_stats(self) -> dict[str, int | float | str | bool]:
        """Sharded-serving fields for the receipt (ISSUE 15): tp size +
        mesh shape (config: ``tp`` / ``mesh_shape``) and the PER-CHIP
        KV footprint (shard sizes, the honest HBM claim).
        ``tp_collectives`` / ``tp_hlo_ok`` appear only after an explicit
        :meth:`audit_decode_hlo` (outcomes). Host metadata only —
        sharding math, no device fetch."""
        if not self._shard:
            return {"tp": 1}
        out: dict[str, int | float | str | bool] = {
            "tp": self._tp,
            "mesh_shape": ",".join(
                f"{k}:{v}"
                for k, v in self._strategy.mesh.shape.items()
            ),
            "tp_kv_bytes_per_chip": self._nbytes(self._state["cache"]),
        }
        if self._tp_audit is not None:
            out["tp_collectives"] = sum(
                self._tp_audit["collectives"].values()
            )
            out["tp_hlo_ok"] = self._tp_audit["ok"]
        return out

    def role_stats(self) -> dict[str, int | str]:
        """Disaggregation fields for the receipt (ISSUE 18): the
        engine's role (config) plus the handoff counters (outcomes).
        ``{"role": 0}`` when monolithic."""
        if self._role is None:
            return {"role": 0}
        return {
            "role": self._role,
            "handoffs_out": self.n_handoffs_out,
            "handoffs_in": self.n_handoffs_in,
        }

    def sentry_stats(self) -> dict[str, int | float]:
        """Contract-sentry fields for the receipt (ISSUE 19): the
        ``sentry`` flag is config; compile / fetch / re-upload counters
        are outcomes. ``{"sentry": 0}`` when off. A fleet sharing ONE
        sentry reports fleet-global numbers — ``FleetRouter.stats()``
        dedupes by sentry identity instead of summing the same counters
        once per replica."""
        if self._sentry is None:
            return {"sentry": 0}
        return self._sentry.summary()

    def slo_stats(self) -> dict[str, int | float]:
        """SLO-tier fields for the receipt (ISSUE 20):
        ``priority_classes`` / ``preemption`` are config; the swap
        counters are outcomes. ``{"priority_classes": 0}`` when off."""
        if not self._slo:
            return {"priority_classes": 0}
        return {
            "priority_classes": self._n_classes,
            "preemption": 1,
            "n_preemptions": self.n_swaps_out,
            "n_swaps_out": self.n_swaps_out,
            "n_swaps_in": self.n_swaps_in,
            "swapped_now": len(self._swapped),
        }

    def slot_stats(self) -> dict[str, int | float]:
        """Bytes ONE slot holds of the cache tree, by what the leaf is
        (:func:`.slots.slot_bytes`: from shapes, no device fetch):
        ``slot_kv_bytes`` (K and V over the whole window: the heads of an
        attention layer, a latent, the one shared cache of a model with
        recurrent state), ``slot_ring_bytes`` (rings of the newest rows of
        window layers) and ``slot_state_bytes`` (recurrent state and the
        convolutions' tails). A paged pool is no slot's: its pages count
        under ``pages``."""
        return slot_bytes(self._state["cache"], self.n_slots)

    _STATS_PARTS = (
        "prefix", "spec", "adapters", "fault", "flight", "pipeline",
        "pages", "tp", "role", "sentry", "slo", "slot",
    )

    def stats(self, *parts: str) -> dict[str, int | float]:
        """ONE aggregate over every per-subsystem stats dict — the
        receipt/selftest call sites used to re-assemble these by hand.
        ``stats()`` returns everything; ``stats("spec", "fault")``
        selects subsystems (multi-engine callers merge stats from
        DIFFERENT engines, and an unfiltered merge would clobber e.g.
        one engine's ``prefix_cache: 1`` with another's ``0``). Key sets
        are disjoint across subsystems, so the full merge is lossless."""
        chosen = parts or self._STATS_PARTS
        unknown = set(chosen) - set(self._STATS_PARTS)
        if unknown:
            raise ValueError(
                f"unknown stats parts {sorted(unknown)}; "
                f"known: {list(self._STATS_PARTS)}"
            )
        fns = {
            "prefix": self.prefix_stats,
            "spec": self.spec_stats,
            "adapters": self.adapter_stats,
            "fault": self.fault_stats,
            "flight": self.flight_stats,
            "pipeline": self.pipeline_stats,
            "pages": self.page_stats,
            "tp": self.tp_stats,
            "role": self.role_stats,
            "sentry": self.sentry_stats,
            "slo": self.slo_stats,
            "slot": self.slot_stats,
        }
        out: dict[str, int | float] = {}
        for part in self._STATS_PARTS:
            if part in chosen:
                out.update(fns[part]())
        return out


def _whole_slots_only(model: str, asked) -> None:
    """Refuse, in words, every ``(what, on)`` of ``asked`` that is on: the
    cache of ``model`` is known to the whole-slot path alone so far."""
    refused = [what for what, on in asked if on]
    if refused:
        raise ValueError(
            f"{model} is served from whole slots only; this engine was "
            "asked for " + "; ".join(refused)
        )


def _seed_history(state, tokens, p_len, slot, first):
    """Reset slot ``slot``'s n-gram history to [prompt, first token]:
    the bucket-padded prompt row lands whole (junk beyond ``p_len`` is
    masked by ``hist_len`` in :func:`..models.sampling.ngram_draft`),
    the first sampled token overwrites the pad at position ``p_len``.
    ``slot`` / ``p_len`` are traced — no compile per slot or length."""
    hist = jax.lax.dynamic_update_slice(
        state["hist"], tokens, (slot, 0)
    )
    hist = hist.at[slot, p_len].set(first)
    return {
        "hist": hist,
        "hist_len": state["hist_len"].at[slot].set(p_len + 1),
    }


def _park_slot(remaining, slot):
    """Zero one slot's device-side budget counter (host freed it early)."""
    return remaining.at[slot].set(0)

"""The continuous-batching serving engine (serve/).

The load-bearing pins:

- greedy continuous-batching output is TOKEN-EXACT vs one-shot
  ``generate()`` for staggered arrivals with mixed prompt lengths — slot
  refill, bucketed prefill, per-slot positions, and chained decode must
  be invisible in the outputs (the ISSUE 5 acceptance criterion), across
  the unrolled, ``scan_layers``, and GQA layouts;
- a monkeypatched ``jax.device_get`` proves the fetch discipline: ONE
  batched host fetch per ``tokens_per_launch``-step decode chain plus one
  scalar per prefill — never a per-token sync (the per-LAUNCH floor is
  the whole point of chaining, CLAUDE.md);
- scheduler edge cases: slot exhaustion + ``QueueFull`` backpressure,
  admission rejects requests that can never fit the window, FIFO order,
  a request finishing mid-chain, ``max_new_tokens == 1`` (completes at
  prefill, no decode chain at all), and EOS early-stop with slot parking;
- sampled requests are reproducible functions of their OWN seed — the
  same request returns the same tokens no matter what else shares the
  batch (per-slot PRNG streams, models/sampling.py);
- the radix prefix cache (``prefix_cache_bytes``, ISSUE 6) is INVISIBLE
  in the tokens: streams with 50–90% shared prefixes are byte-identical
  greedy cache-on vs cache-off (across the plain, ``scan_layers``, and
  GQA cache layouts), while full prefills measurably DROP (counted, not
  estimated — splices replace them), the fetch budget extends by exactly
  one scalar per splice, and forced LRU eviction under a tiny byte
  budget changes counters, never tokens;
- self-speculative decoding (``speculative_k``, ISSUE 7) is INVISIBLE
  in greedy tokens: speculate-k streams are byte-identical to the
  non-speculative engine, to one-shot ``generate()``, and to
  ``generate(..., speculative_k=...)`` across the unrolled,
  ``scan_layers``, GQA, and int8-KV layouts, including finish-mid-chain
  and composed with prefix-cache splices (both share the vector
  ``cache_index`` rewind machinery); the fetch budget is UNCHANGED with
  ``spec_k > 1`` (the (S, T, k+1) block + counts ride the chain's one
  batched fetch); and the mechanism visibly fires on a repetitive
  stream — mean accepted length > 1, sequential verify forwards <
  tokens emitted;
- multi-tenant LoRA serving (``adapter_bank=...``, ISSUE 8) is INVISIBLE
  in co-batching: a mixed-tenant stream is byte-identical to dedicated
  single-tenant engines over the same bank (across the unrolled,
  ``scan_layers``, GQA, and int8-KV layouts, composed with prefix
  splices and speculation), id 0 through a bank matches the bank-less
  base engine and ``generate()`` exactly, NOTHING recompiles after
  warmup when tenants mix (the adapter id is data, not a trace
  constant), the fetch budget is unchanged, admission rejects dead ids
  at submit, and prefix-cache keys are tenant-scoped — two tenants
  sharing a prompt never splice from each other's cache;
- the robustness layer (ISSUE 9) is INVISIBLE until a fault lands:
  guard/deadline-on engines with no faults are byte-identical to the
  plain engine and ``generate()`` with zero extra compiles and the
  UNCHANGED fetch budget (chains + prefills + splices); an injected
  NaN (``utils.chaos``) quarantines exactly the poisoned slot
  (``"nonfinite"``, pre-poison tokens kept) while co-scheduled slots
  stay token-identical to a clean run; deadlines and host-side
  ``cancel`` complete at chain/refill boundaries only; ``close`` /
  ``drain`` give ``QueueClosed`` backpressure and run every accepted
  request to completion; a prefill that raises is isolated to its
  request (``"error"``) and the engine keeps serving;
- request-loop pipelining (ISSUE 11) is INVISIBLE in the tokens:
  ``pipeline_depth=2`` double-buffers decode chains (chain ``i+1``
  dispatched BEFORE chain ``i``'s batched fetch — an ordering test on a
  monkeypatched dispatch/fetch log proves it, not just the counters) and
  ``prefill_chunk=N`` streams long prompts through bounded chunks
  interleaved with decode; both are byte-identical greedy to the serial
  engine and ``generate()`` across all four cache layouts, composed with
  splices + speculation + adapters, the fetch budget stays EXACTLY
  chains + prefills + splices (mid chunks are pure dispatch), deadlines
  and ``cancel`` fire at the OBSERVED chain boundary keeping fetched
  tokens, a co-scheduled short request is never starved behind a long
  chunked prefill, and depth-1/chunk-0 engines keep byte-identical
  state trees and compiled-program counts;
- fleet resilience (ISSUE 12) is INVISIBLE in the tokens: an N=1
  ``FleetRouter`` is a transparent wrapper (byte-identical completions,
  slot-state trees, and compiled-program counts vs driving the engine
  directly), a real-engine fleet composed with prefix caching +
  multi-tenancy + pipelining is token-exact to the single engine with
  the summed per-replica fetch budget intact, and a chaos-killed
  replica's queued work re-dispatches token-identically with the
  ``DispatchLedger`` verifying exactly-once delivery;
- sharded serving (ISSUE 15) rides the same machinery: the
  ``--selftest --tp 2`` arm replays the base stream through a
  head-sharded engine and pins token-exactness, the unchanged fetch
  budget, the all-reduce-only decode HLO audit, and per-chip KV bytes
  at 1/tp of global (tests/test_tp_serve.py holds the in-process
  pins);
- SLO tiers (ISSUE 20) are INVISIBLE until traffic contends:
  ``priority_classes=0`` engines keep byte-identical state trees and
  compiled-program counts (no swap programs built, the attrs don't
  exist), and when a class-0 arrival forces a chain-boundary KV-swap
  preemption the fetch budget grows by EXACTLY the counted swap-outs —
  chains + prefills + splices + swaps, the monkeypatch spy here and
  tests/test_slo.py's roundtrip pins hold the rest;
- ``python -m pytorch_distributed_training_tutorials_tpu.serve --selftest`` succeeds in a
  subprocess (the tier-1 wiring for the end-to-end smoke), and the
  ``--chaos`` / ``--router`` / ``--slo`` arms exercise the fault,
  fleet, and preemption paths end to end.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_training_tutorials_tpu.models.generate import generate
from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from pytorch_distributed_training_tutorials_tpu.serve import (
    QueueFull,
    Request,
    ServeEngine,
    bucket_len,
)

REPO = Path(__file__).resolve().parents[1]

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, max_seq_len=64
)


def _make(cfg=CFG, seed=0):
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    return model, params


def _prompt(seed, p_len, vocab=CFG.vocab_size):
    return jax.device_get(
        jax.random.randint(jax.random.PRNGKey(seed), (p_len,), 0, vocab)
    ).tolist()


def _reference(model, params, prompt, max_new):
    """One-shot greedy generate(), new tokens only."""
    out = generate(model, params, jnp.asarray([prompt], jnp.int32), max_new)
    return jax.device_get(out)[0, len(prompt):].tolist()


@pytest.fixture(scope="module")
def model_params():
    return _make()


# ------------------------------------------------- the acceptance criterion

def test_token_exact_staggered_mixed_lengths(model_params):
    """2 slots, 5 staggered requests with mixed prompt lengths/budgets:
    every completion matches one-shot generate() token for token."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=8)
    reqs = [(3, 9), (7, 12), (5, 5), (12, 6), (2, 17)]
    prompts = [_prompt(100 + i, p) for i, (p, _) in enumerate(reqs)]
    # two submitted up front; the rest arrive between scheduling rounds
    ids = [
        engine.submit(Request(prompt=prompts[i], max_new_tokens=reqs[i][1]))
        for i in range(2)
    ]
    pending = list(range(2, len(reqs)))
    completions = {}
    while not engine.idle or pending:
        if pending:
            i = pending.pop(0)
            ids.append(
                engine.submit(
                    Request(prompt=prompts[i], max_new_tokens=reqs[i][1])
                )
            )
        for c in engine.step():
            completions[c.request_id] = c
    assert sorted(completions) == sorted(ids)
    for i, (p_len, max_new) in enumerate(reqs):
        ref = _reference(model, params, prompts[i], max_new)
        got = completions[ids[i]].tokens
        assert got == ref, f"request {i}: {got} != {ref}"
        assert completions[ids[i]].finish_reason == "length"
        assert completions[ids[i]].latency_s > 0


@pytest.mark.parametrize(
    "cfg_kwargs",
    [
        dict(scan_layers=True),
        dict(n_kv_heads=2),
    ],
    ids=["scan_layers", "gqa"],
)
def test_token_exact_variant_layouts(cfg_kwargs):
    """The slot surgery handles the nn.scan-stacked cache (leading layer
    axis on every leaf) and the GQA-shrunk cache the same as the plain
    layout: still token-exact vs generate()."""
    import dataclasses

    cfg = dataclasses.replace(CFG, **cfg_kwargs)
    model, params = _make(cfg)
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=8)
    reqs = [(4, 10), (9, 7), (6, 12)]
    prompts = [_prompt(200 + i, p) for i, (p, _) in enumerate(reqs)]
    ids = [
        engine.submit(Request(prompt=prompts[i], max_new_tokens=m))
        for i, (_, m) in enumerate(reqs)
    ]
    completions = {c.request_id: c for c in engine.run_until_idle()}
    for i, (_, max_new) in enumerate(reqs):
        ref = _reference(model, params, prompts[i], max_new)
        assert completions[ids[i]].tokens == ref


def test_int8_kv_cache_smoke():
    """int8 KV storage (per-position scales ride the same slot surgery):
    the engine runs and respects budgets. Exactness vs generate() is not
    pinned here — the rounded cache makes near-ties layout-sensitive
    (CLAUDE.md's kv_cache_dtype caveat)."""
    import dataclasses

    cfg = dataclasses.replace(CFG, kv_cache_dtype=jnp.int8)
    model, params = _make(cfg)
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=8)
    ids = [
        engine.submit(
            Request(prompt=_prompt(300 + i, 5 + i), max_new_tokens=6 + i)
        )
        for i in range(3)
    ]
    completions = {c.request_id: c for c in engine.run_until_idle()}
    for i, rid in enumerate(ids):
        assert len(completions[rid].tokens) == 6 + i
        assert all(
            0 <= t < cfg.vocab_size for t in completions[rid].tokens
        )


# --------------------------------------------------------- fetch discipline

def test_one_fetch_per_chain(model_params, monkeypatch):
    """<= 1 host fetch per tokens_per_launch-step decode chain (plus one
    scalar per prefill): the no-per-token-sync contract, counted by
    monkeypatching jax.device_get — the one attribute the engine fetches
    through."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=8)
    prompts = [_prompt(400 + i, 4 + 3 * i) for i in range(3)]
    calls = {"n": 0}
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get",
        lambda x: (calls.__setitem__("n", calls["n"] + 1), real_get(x))[1],
    )
    for p in prompts:
        engine.submit(Request(prompt=p, max_new_tokens=20))
    completions = engine.run_until_idle()
    assert len(completions) == 3
    assert engine.n_chains >= 3  # 20 tokens at 8/launch, multiple rounds
    # the whole run: one fetch per chain + one per prefill, nothing else
    assert calls["n"] == engine.n_chains + engine.n_prefills
    total_tokens = sum(len(c.tokens) for c in completions)
    assert total_tokens == 60
    # amortization: far fewer fetches than generated tokens
    assert calls["n"] * engine.tokens_per_launch >= total_tokens


# ------------------------------------------------- scheduler + admission

def test_backpressure_queue_full(model_params):
    model, params = model_params
    engine = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=8, max_queue=2
    )
    for i in range(2):
        engine.submit(Request(prompt=_prompt(500 + i, 3), max_new_tokens=4))
    with pytest.raises(QueueFull):
        engine.submit(Request(prompt=_prompt(502, 3), max_new_tokens=4))
    # draining frees queue capacity: the same request is admissible after
    done = engine.run_until_idle()
    assert len(done) == 2
    rid = engine.submit(Request(prompt=_prompt(502, 3), max_new_tokens=4))
    assert rid == 2
    assert len(engine.run_until_idle()) == 1


def test_admission_validation(model_params):
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=1)
    with pytest.raises(ValueError):
        engine.submit(Request(prompt=[], max_new_tokens=4))
    with pytest.raises(ValueError):
        engine.submit(Request(prompt=[1, 2], max_new_tokens=0))
    with pytest.raises(ValueError):  # can never fit the 64-token window
        engine.submit(Request(prompt=[1] * 30, max_new_tokens=40))
    assert engine.idle  # nothing slipped into the queue


def test_fifo_order(model_params):
    """Same-shape requests complete in arrival order on one slot."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=1, tokens_per_launch=8)
    ids = [
        engine.submit(Request(prompt=_prompt(600 + i, 4), max_new_tokens=3))
        for i in range(3)
    ]
    done = engine.run_until_idle()
    assert [c.request_id for c in done] == ids


def test_finish_mid_chain(model_params):
    """A budget that is not a chain multiple finishes mid-chain; surplus
    chain tokens are discarded and a co-scheduled longer request stays
    token-exact."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=8)
    p_short, p_long = _prompt(700, 5), _prompt(701, 6)
    i_short = engine.submit(Request(prompt=p_short, max_new_tokens=3))
    i_long = engine.submit(Request(prompt=p_long, max_new_tokens=19))
    completions = {c.request_id: c for c in engine.run_until_idle()}
    assert completions[i_short].tokens == _reference(
        model, params, p_short, 3
    )
    assert completions[i_long].tokens == _reference(
        model, params, p_long, 19
    )


def test_max_new_tokens_one(model_params):
    """max_new_tokens == 1 completes straight out of prefill — the decode
    chain never runs."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=1, tokens_per_launch=8)
    prompt = _prompt(800, 6)
    rid = engine.submit(Request(prompt=prompt, max_new_tokens=1))
    done = engine.step()
    assert [c.request_id for c in done] == [rid]
    assert done[0].tokens == _reference(model, params, prompt, 1)
    assert done[0].finish_reason == "length"
    assert engine.n_chains == 0
    assert engine.idle


def test_eos_early_stop(model_params):
    """EOS sampled mid-stream stops the request (stop token included),
    parks the slot, and the engine keeps serving: a follow-up request on
    the freed slot is still token-exact."""
    model, params = model_params
    prompt = _prompt(900, 5)
    ref = _reference(model, params, prompt, 12)
    eos = ref[4]  # force a stop 5 tokens in
    stop_at = ref.index(eos) + 1  # first occurrence wins
    engine = ServeEngine(model, params, n_slots=1, tokens_per_launch=8)
    rid = engine.submit(
        Request(prompt=prompt, max_new_tokens=12, eos_token=eos)
    )
    done = engine.run_until_idle()
    assert [c.request_id for c in done] == [rid]
    assert done[0].finish_reason == "eos"
    assert done[0].tokens == ref[:stop_at]
    # the freed (parked) slot serves the next request exactly
    p2 = _prompt(901, 7)
    engine.submit(Request(prompt=p2, max_new_tokens=6))
    done2 = engine.run_until_idle()
    assert done2[0].tokens == _reference(model, params, p2, 6)


def test_eos_at_first_token(model_params):
    """EOS on the prefill-sampled token completes without any chain."""
    model, params = model_params
    prompt = _prompt(902, 4)
    first = _reference(model, params, prompt, 1)[0]
    engine = ServeEngine(model, params, n_slots=1, tokens_per_launch=8)
    engine.submit(
        Request(prompt=prompt, max_new_tokens=9, eos_token=first)
    )
    done = engine.step()
    assert done[0].finish_reason == "eos"
    assert done[0].tokens == [first]
    assert engine.n_chains == 0
    assert engine.idle


# ------------------------------------------------------------- sampling

def test_sampled_tokens_reproducible_per_seed(model_params):
    """temperature > 0: a request's tokens are a function of its own seed
    — identical whether it runs alone or co-scheduled with strangers."""
    model, params = model_params
    prompt = _prompt(1000, 5)
    req = dict(prompt=prompt, max_new_tokens=10, seed=7)

    engine_solo = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, temperature=1.0
    )
    rid = engine_solo.submit(Request(**req))
    solo = {c.request_id: c for c in engine_solo.run_until_idle()}[rid]

    engine_busy = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, temperature=1.0
    )
    engine_busy.submit(
        Request(prompt=_prompt(1001, 9), max_new_tokens=14, seed=3)
    )
    rid_busy = engine_busy.submit(Request(**req))
    engine_busy.submit(
        Request(prompt=_prompt(1002, 3), max_new_tokens=6, seed=11)
    )
    busy = {c.request_id: c for c in engine_busy.run_until_idle()}[rid_busy]

    assert solo.tokens == busy.tokens
    # and a different seed actually changes the draw stream
    engine_other = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, temperature=1.0
    )
    rid2 = engine_other.submit(Request(**{**req, "seed": 8}))
    other = {c.request_id: c for c in engine_other.run_until_idle()}[rid2]
    assert other.tokens != solo.tokens


# ------------------------------------------------------------- slot utils

def test_bucket_len():
    assert bucket_len(1, 64) == 8
    assert bucket_len(8, 64) == 8
    assert bucket_len(9, 64) == 16
    assert bucket_len(33, 64) == 64
    assert bucket_len(60, 64) == 64
    assert bucket_len(5, 6) == 6  # capped at a non-pow2 window
    with pytest.raises(ValueError):
        bucket_len(0, 64)


def test_bucketing_reuses_compiles(model_params):
    """Prompt lengths inside one bucket share a prefill compile: serving
    many distinct lengths traces at most one program per bucket."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=1, tokens_per_launch=8)
    for i, p_len in enumerate([3, 5, 8, 11, 16, 2]):  # buckets {8, 16}
        engine.submit(
            Request(prompt=_prompt(1100 + i, p_len), max_new_tokens=2)
        )
    engine.run_until_idle()
    # jit caches per tokens shape: (1, 8) and (1, 16) only
    assert engine._prefill._cache_size() == 2


# ------------------------------------------------------- radix prefix cache

def _overlap_stream(overlap, n_requests=8, lengths=(6, 10, 14), seed=42):
    """A synthetic shared-prefix stream: request i's prompt is the first
    ``round(overlap * p_len)`` tokens of ONE shared family plus a random
    tail — the shared-system-prompt workload the prefix cache targets
    (the same construction examples/serve_llm_int8.py --prefix-overlap
    uses)."""
    import numpy as np

    rng = np.random.Generator(np.random.PCG64(seed))
    shared = rng.integers(0, CFG.vocab_size, (max(lengths),)).tolist()
    reqs = []
    for i in range(n_requests):
        p_len = lengths[i % len(lengths)]
        k = min(p_len, int(round(overlap * p_len)))
        tail = rng.integers(0, CFG.vocab_size, (p_len - k,)).tolist()
        reqs.append((shared[:k] + tail, 5 + (i % 3)))
    return reqs


def _run_stream(model, params, reqs, **engine_kwargs):
    """Staggered submit (2 up front, one per scheduling round after) —
    completions keyed by request id, plus the engine for its counters."""
    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, **engine_kwargs
    )
    ids = [
        engine.submit(Request(prompt=p, max_new_tokens=m, seed=i))
        for i, (p, m) in enumerate(reqs[:2])
    ]
    pending = list(range(2, len(reqs)))
    completions = {}
    while not engine.idle or pending:
        if pending:
            i = pending.pop(0)
            p, m = reqs[i]
            ids.append(engine.submit(Request(prompt=p, max_new_tokens=m,
                                             seed=i)))
        for c in engine.step():
            completions[c.request_id] = c
    return engine, [completions[rid] for rid in ids]


@pytest.mark.parametrize("overlap", [0.5, 0.7, 0.9])
def test_prefix_cache_token_exact_and_prefills_drop(model_params, overlap):
    """The ISSUE 6 acceptance pin: on a staggered stream with 50–90%
    shared prefixes, cache-on output is byte-identical greedy to
    cache-off, while counted full-prefill launches DROP (splices replace
    them) and the hit rate is > 0. At 0.7 this is the criterion's
    synthetic 70%-overlap stream."""
    model, params = model_params
    reqs = _overlap_stream(overlap)
    eng_off, out_off = _run_stream(model, params, reqs)
    eng_on, out_on = _run_stream(
        model, params, reqs, prefix_cache_bytes=16 * 1024 * 1024
    )
    assert [c.tokens for c in out_on] == [c.tokens for c in out_off]
    # counted, not estimated: splices replaced full prefills
    assert eng_on.n_prefills < eng_off.n_prefills
    assert eng_on.n_splices >= 1
    assert eng_on.n_prefills + eng_on.n_splices == eng_off.n_prefills
    stats = eng_on.prefix_stats()
    assert stats["prefix_hit_rate"] > 0
    assert stats["prefix_hit_tokens"] > 0
    # every completion carries a fetch-backed TTFT
    assert all(c.ttft_s > 0 for c in out_on)
    # the cache-off engine reports itself off
    assert eng_off.prefix_stats() == {"prefix_cache": 0}


@pytest.mark.parametrize(
    "cfg_kwargs",
    [
        dict(scan_layers=True),
        dict(n_kv_heads=2),
    ],
    ids=["scan_layers", "gqa"],
)
def test_prefix_cache_variant_layouts(cfg_kwargs):
    """Segment extraction / seeding handle the nn.scan-stacked cache
    (seq axis 2, after the layer axis) and the GQA-shrunk cache: spliced
    requests stay token-exact vs one-shot generate()."""
    import dataclasses

    cfg = dataclasses.replace(CFG, **cfg_kwargs)
    model, params = _make(cfg)
    reqs = _overlap_stream(0.7, n_requests=6)
    engine, out = _run_stream(
        model, params, reqs, prefix_cache_bytes=16 * 1024 * 1024
    )
    assert engine.n_splices >= 1  # the splice path actually ran
    for (prompt, max_new), c in zip(reqs, out):
        assert c.tokens == _reference(model, params, prompt, max_new)


def test_prefix_cache_fetch_budget(model_params, monkeypatch):
    """A splice costs exactly what a prefill costs on the host side: one
    scalar fetch for the first sampled token. The whole overlap stream
    stays inside chains + prefills + splices — no hidden syncs in the
    index, the acquire/release pinning, or the segment plumbing."""
    model, params = model_params
    calls = {"n": 0}
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get",
        lambda x: (calls.__setitem__("n", calls["n"] + 1), real_get(x))[1],
    )
    engine, out = _run_stream(
        model, params, _overlap_stream(0.7),
        prefix_cache_bytes=16 * 1024 * 1024,
    )
    assert len(out) == 8 and engine.n_splices >= 1
    assert calls["n"] == (
        engine.n_chains + engine.n_prefills + engine.n_splices
    )


def test_prefix_cache_eviction_under_pressure_stays_exact(model_params):
    """A byte budget too small for the stream's working set forces LRU
    eviction mid-stream (between chains, by construction — inserts only
    happen at slot refill): counters move, tokens don't."""
    from pytorch_distributed_training_tutorials_tpu.serve import tree_nbytes

    model, params = model_params
    reqs = _overlap_stream(0.5, n_requests=8)
    eng_off, out_off = _run_stream(model, params, reqs)
    # size the budget to ~2.5 of the LARGEST segment: a couple of inserts
    # fit, then every later one must evict a cold resident (at most 2 of
    # the stream's 8 distinct keys are pinned at once on 2 slots, so an
    # unpinned victim always exists)
    longest = max(reqs, key=lambda r: len(r[0]))[0]
    probe = ServeEngine(
        model, params, n_slots=1, prefix_cache_bytes=1 << 30
    )
    probe.submit(Request(prompt=longest, max_new_tokens=1))
    probe.run_until_idle()
    seg_bytes = max(tree_nbytes(s.handle) for s in probe.prefix.segments())
    eng_on, out_on = _run_stream(
        model, params, reqs, prefix_cache_bytes=int(seg_bytes * 2.5)
    )
    assert [c.tokens for c in out_on] == [c.tokens for c in out_off]
    assert eng_on.prefix_stats()["prefix_evicted_bytes"] > 0


def test_prefix_cache_multi_turn_deepens_the_index(model_params):
    """The multi-turn shape: each turn's prompt extends the previous
    prompt + its reply. Turn 2 must splice (not full-prefill) and stay
    token-exact — grow-on-splice keeps deepening the index."""
    model, params = model_params
    turn1 = _prompt(1200, 9)
    engine = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=8,
        prefix_cache_bytes=16 * 1024 * 1024,
    )
    rid1 = engine.submit(Request(prompt=turn1, max_new_tokens=6))
    reply = {c.request_id: c for c in engine.run_until_idle()}[rid1].tokens
    turn2 = turn1 + reply + _prompt(1201, 4)
    rid2 = engine.submit(Request(prompt=turn2, max_new_tokens=6))
    got = {c.request_id: c for c in engine.run_until_idle()}[rid2].tokens
    assert engine.n_splices == 1 and engine.n_prefills == 1
    # the hit covered at least the whole first turn's prompt
    assert engine.prefix_hit_tokens >= len(turn1)
    assert got == _reference(model, params, turn2, 6)
    # ...and turn 2's own full prompt is now resident for turn 3
    assert tuple(turn2) in engine.prefix


# ------------------------------------------- self-speculative decoding

def _template_stream(n_requests=5, seed=21):
    """A repetitive/templated prompt stream (the prompt-lookup workload):
    each prompt is a short template tiled a few times plus a distinct
    suffix token, with mixed budgets."""
    template = [7, 8, 9, 10, 11]
    return [
        (template * (3 + i % 2) + [20 + i + seed], 10 + 3 * (i % 3))
        for i in range(n_requests)
    ]


@pytest.mark.slow
def test_spec_token_exact_staggered(model_params):
    """The ISSUE 7 acceptance pin: a staggered speculate-k stream is
    byte-identical greedy to the non-speculative engine, to one-shot
    generate(), and to generate(speculative_k=...) — speculation changes
    the step count, never the tokens."""
    model, params = model_params
    reqs = [(_prompt(1300 + i, p), m)
            for i, (p, m) in enumerate([(3, 9), (7, 12), (5, 5), (12, 6)])]
    reqs += _template_stream(2)
    eng_off, out_off = _run_stream(model, params, reqs)
    eng_on, out_on = _run_stream(model, params, reqs, speculative_k=3)
    assert [c.tokens for c in out_on] == [c.tokens for c in out_off]
    for (prompt, max_new), c in zip(reqs, out_on):
        assert c.tokens == _reference(model, params, prompt, max_new)
        spec_ref = jax.device_get(generate(
            model, params, jnp.asarray([prompt], jnp.int32), max_new,
            speculative_k=3,
        ))[0, len(prompt):].tolist()
        assert c.tokens == spec_ref


@pytest.mark.parametrize(
    "cfg_kwargs",
    [
        dict(scan_layers=True),
        dict(n_kv_heads=2),
    ],
    ids=["scan_layers", "gqa"],
)
def test_spec_variant_layouts(cfg_kwargs):
    """The draft/verify/rewind machinery rides the nn.scan-stacked cache
    ((L, S) position counters) and the GQA-shrunk cache identically:
    still token-exact vs generate()."""
    import dataclasses

    cfg = dataclasses.replace(CFG, **cfg_kwargs)
    model, params = _make(cfg)
    reqs = _template_stream(4)
    engine, out = _run_stream(model, params, reqs, speculative_k=2)
    for (prompt, max_new), c in zip(reqs, out):
        assert c.tokens == _reference(model, params, prompt, max_new)
    # the templated stream must actually exercise acceptance
    assert engine.spec_stats()["spec_drafts_accepted"] > 0


def test_spec_int8_kv_matches_nonspec_engine():
    """int8 KV: speculative and non-speculative engines quantize at the
    same positions with the same values (the rewind only moves counters,
    accepted K/V rows are written once), so the streams stay
    byte-identical even where generate()-exactness is off the table
    (CLAUDE.md's kv_cache_dtype near-tie caveat)."""
    import dataclasses

    cfg = dataclasses.replace(CFG, kv_cache_dtype=jnp.int8)
    model, params = _make(cfg)
    reqs = [(_prompt(1400 + i, 4 + i), 8 + i) for i in range(3)]
    reqs += _template_stream(2, seed=60)
    _, out_off = _run_stream(model, params, reqs)
    _, out_on = _run_stream(model, params, reqs, speculative_k=3)
    assert [c.tokens for c in out_on] == [c.tokens for c in out_off]


def test_spec_finish_mid_chain_and_eos(model_params):
    """Budgets that end inside a verify block: surplus accepted tokens
    are discarded at the budget exactly like generate() truncating, and
    EOS inside an accepted block stops at the EOS token and parks the
    slot while a co-scheduled request stays exact."""
    model, params = model_params
    p_short, p_long = [7, 8, 9] * 3, _prompt(1500, 6)
    ref_short = _reference(model, params, p_short, 12)
    eos = ref_short[4]
    stop_at = ref_short.index(eos) + 1
    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, speculative_k=3
    )
    i_short = engine.submit(
        Request(prompt=p_short, max_new_tokens=12, eos_token=eos)
    )
    i_long = engine.submit(Request(prompt=p_long, max_new_tokens=19))
    completions = {c.request_id: c for c in engine.run_until_idle()}
    assert completions[i_short].finish_reason == "eos"
    assert completions[i_short].tokens == ref_short[:stop_at]
    assert completions[i_long].tokens == _reference(
        model, params, p_long, 19
    )


def test_spec_fetch_budget(model_params, monkeypatch):
    """The no-per-token-sync contract with spec_k > 1: the (S, T, k+1)
    block and the per-step counts come back in the chain's ONE batched
    fetch — the whole speculative stream still costs exactly one fetch
    per chain plus one scalar per prefill."""
    model, params = model_params
    calls = {"n": 0}
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get",
        lambda x: (calls.__setitem__("n", calls["n"] + 1), real_get(x))[1],
    )
    engine, out = _run_stream(
        model, params, _template_stream(5), speculative_k=3
    )
    assert len(out) == 5
    assert calls["n"] == engine.n_chains + engine.n_prefills


def test_spec_prefix_splice_composed(model_params):
    """Prefix-cache splices and speculation share the vector cache_index
    machinery; composed they must still be invisible: spliced speculative
    streams byte-identical to the plain engine, with both mechanisms
    measurably firing."""
    model, params = model_params
    reqs = _overlap_stream(0.7)
    _, out_plain = _run_stream(model, params, reqs)
    engine, out = _run_stream(
        model, params, reqs, speculative_k=2,
        prefix_cache_bytes=16 * 1024 * 1024,
    )
    assert [c.tokens for c in out] == [c.tokens for c in out_plain]
    assert engine.n_splices >= 1
    assert engine.spec_stats()["spec_steps_consumed"] > 0


def test_spec_sampled_reproducible_per_seed(model_params):
    """temperature > 0 under speculation: per-request streams are still a
    function of the request's own seed, co-scheduling invisible."""
    model, params = model_params
    prompt = [3, 4, 5] * 3
    req = dict(prompt=prompt, max_new_tokens=10, seed=7)
    kw = dict(tokens_per_launch=8, temperature=1.0, speculative_k=2)

    solo_eng = ServeEngine(model, params, n_slots=2, **kw)
    rid = solo_eng.submit(Request(**req))
    solo = {c.request_id: c for c in solo_eng.run_until_idle()}[rid]

    busy_eng = ServeEngine(model, params, n_slots=2, **kw)
    busy_eng.submit(Request(prompt=_prompt(1600, 9), max_new_tokens=14,
                            seed=3))
    rid_busy = busy_eng.submit(Request(**req))
    busy = {c.request_id: c for c in busy_eng.run_until_idle()}[rid_busy]
    assert solo.tokens == busy.tokens
    assert all(0 <= t < CFG.vocab_size for t in solo.tokens)


def test_spec_mechanism_fires_on_repetitive_stream(model_params):
    """The perf mechanism, counted not estimated: on a templated stream
    the mean accepted length exceeds 1 and the number of SEQUENTIAL
    verify forwards is strictly below the tokens emitted — speculation
    bought tokens without sequential steps (the only lever left at the
    decode roofline, ISSUE 7 / ROADMAP item 2)."""
    model, params = model_params
    engine, out = _run_stream(
        model, params, _template_stream(4), speculative_k=4
    )
    stats = engine.spec_stats()
    assert stats["spec_mean_accepted_len"] > 1.0
    assert stats["n_verify_forwards"] < engine.generated_tokens
    assert stats["spec_acceptance_rate"] > 0
    # the off engine reports itself off
    assert ServeEngine(model, params).spec_stats() == {"speculative": 0}


def test_spec_validation(model_params):
    model, params = model_params
    with pytest.raises(ValueError):
        ServeEngine(model, params, speculative_k=-1)
    with pytest.raises(ValueError):
        ServeEngine(model, params, speculative_k=2, spec_ngram=0)
    with pytest.raises(ValueError):  # k + 1 must fit the window
        ServeEngine(model, params, speculative_k=CFG.max_seq_len)


def test_spec_off_state_is_unchanged(model_params):
    """speculative_k=0 keeps the slot-state tree (and so the compiled
    programs) byte-identical to the pre-speculation engine: no history
    buffers, the plain chain."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=2)
    assert set(engine._state) == {"cache", "last_tok", "keys", "remaining"}
    spec = ServeEngine(model, params, n_slots=2, speculative_k=2)
    assert set(spec._state) == {
        "cache", "last_tok", "keys", "remaining", "hist", "hist_len",
    }
    assert spec._state["hist"].shape == (2, CFG.max_seq_len)


# ------------------------------------------------- multi-tenant LoRA serving

def _lora_bank(model, n_adapters=4, rank=4, tenants=(1, 2), scale=0.05):
    """A bank with synthetic tenants: every factor leaf (A and B) filled
    with small per-tenant normals so each row's delta is visible in the
    forward — deterministic per (tenant, leaf-shape) seed, so two banks
    built from the same call are identical."""
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.adapters import AdapterBank

    bank = AdapterBank(model, n_adapters=n_adapters, rank=rank)
    for t in tenants:
        rng = np.random.Generator(np.random.PCG64(1000 + t))
        bank.register(f"tenant-{t}", jax.tree_util.tree_map(
            lambda leaf: jnp.asarray(
                rng.standard_normal(leaf.shape) * scale, leaf.dtype
            ),
            bank.row_zeros(),
        ))
    return bank


@pytest.mark.parametrize(
    "cfg_kwargs",
    [
        dict(),
        # the scan/GQA variants ride the slow tier (tier-1 time budget,
        # ISSUE 11): the unrolled arm pins generate()-exactness and the
        # int8 arm pins the quantized engine-vs-engine contract; the
        # cheaper *_variant_layouts tests keep per-layout coverage fast
        pytest.param(dict(scan_layers=True), marks=pytest.mark.slow),
        pytest.param(dict(n_kv_heads=2), marks=pytest.mark.slow),
        dict(kv_cache_dtype=jnp.int8),
    ],
    ids=["unrolled", "scan_layers", "gqa", "int8_kv"],
)
def test_adapter_mixed_tenants_token_exact(cfg_kwargs):
    """The ISSUE 8 acceptance pin: N >= 3 adapter ids co-batched in one
    engine produce per-request tokens byte-identical to a DEDICATED
    single-tenant engine over the same bank — heterogeneous co-scheduling
    is invisible — and id 0 matches one-shot generate() on the base
    params (skipped on int8-KV, where generate()-exactness is off the
    table per the near-tie caveat; the engine-vs-engine pin still holds
    bitwise there)."""
    import dataclasses

    cfg = dataclasses.replace(CFG, **cfg_kwargs)
    model, params = _make(cfg)
    bank = _lora_bank(model)
    reqs = [(_prompt(2000 + i, 4 + 2 * i), 6 + i, i % 3) for i in range(6)]
    mixed = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, adapter_bank=bank
    )
    ids = [
        mixed.submit(Request(prompt=p, max_new_tokens=m, adapter=a))
        for p, m, a in reqs
    ]
    done = {c.request_id: c for c in mixed.run_until_idle()}
    for aid in (0, 1, 2):
        solo = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8,
            adapter_bank=bank,
        )
        mine = [(i, r) for i, r in enumerate(reqs) if r[2] == aid]
        solo_ids = [
            solo.submit(Request(prompt=p, max_new_tokens=m, adapter=a))
            for _, (p, m, a) in mine
        ]
        solo_done = {c.request_id: c for c in solo.run_until_idle()}
        for (i, (p, m, _)), sid in zip(mine, solo_ids):
            assert done[ids[i]].tokens == solo_done[sid].tokens, (
                f"adapter {aid}, request {i}"
            )
            if aid == 0 and "kv_cache_dtype" not in cfg_kwargs:
                assert done[ids[i]].tokens == _reference(model, params, p, m)
    assert mixed.adapter_stats()["adapter_requests"] == 4  # ids 1 and 2


def test_adapter_zero_recompiles_after_warmup(model_params):
    """The adapter id is DATA: after one warmup request per program
    shape, arbitrary tenant mixes reuse the same compiled prefill/chain
    — jit cache sizes frozen (the zero-recompiles acceptance pin)."""
    model, params = model_params
    bank = _lora_bank(model, tenants=(1, 2, 3))
    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, adapter_bank=bank
    )
    engine.submit(Request(prompt=_prompt(2100, 5), max_new_tokens=6))
    engine.run_until_idle()
    n_prefill = engine._prefill._cache_size()
    n_chain = engine._chain._cache_size()
    for i, aid in enumerate((3, 1, 0, 2, 1, 3)):
        engine.submit(Request(
            prompt=_prompt(2200 + i, 4 + i % 4), max_new_tokens=7,
            adapter=aid,
        ))
    engine.run_until_idle()
    assert engine._prefill._cache_size() == n_prefill == 1
    assert engine._chain._cache_size() == n_chain == 1


def test_adapter_fetch_budget(model_params, monkeypatch):
    """Multi-tenant traffic keeps the fetch discipline bit for bit:
    chains + prefills + splices, nothing per-tenant."""
    model, params = model_params
    bank = _lora_bank(model)
    shared = _prompt(2300, 10)  # prompts built BEFORE counting: _prompt
    prompts = [shared + _prompt(2301 + i, 3) for i in range(6)]  # fetches
    calls = {"n": 0}
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get",
        lambda x: (calls.__setitem__("n", calls["n"] + 1), real_get(x))[1],
    )
    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, adapter_bank=bank,
        prefix_cache_bytes=16 * 1024 * 1024,
    )
    for i, p in enumerate(prompts):
        engine.submit(Request(
            prompt=p, max_new_tokens=8, adapter=i % 3, seed=i,
        ))
    done = engine.run_until_idle()
    assert len(done) == 6
    assert calls["n"] == (
        engine.n_chains + engine.n_prefills + engine.n_splices
    )


def test_adapter_admission_at_submit(model_params):
    """Dead ids bounce synchronously at submit — never mid-decode: out of
    range, unregistered, evicted, and any nonzero id on a bank-less
    engine."""
    model, params = model_params
    plain = ServeEngine(model, params, n_slots=1)
    with pytest.raises(ValueError, match="adapter_bank"):
        plain.submit(Request(prompt=[1, 2], max_new_tokens=2, adapter=1))
    bank = _lora_bank(model, tenants=(1, 2))
    engine = ServeEngine(model, params, n_slots=1, adapter_bank=bank)
    with pytest.raises(ValueError, match="out of range"):
        engine.submit(Request(prompt=[1, 2], max_new_tokens=2, adapter=9))
    with pytest.raises(ValueError, match="not registered"):
        engine.submit(Request(prompt=[1, 2], max_new_tokens=2, adapter=3))
    bank.evict("tenant-2")
    with pytest.raises(ValueError, match="not registered"):
        engine.submit(Request(prompt=[1, 2], max_new_tokens=2, adapter=2))
    assert engine.idle  # nothing slipped into the queue


def test_adapter_off_state_is_unchanged(model_params):
    """No bank -> the slot-state tree (and so the compiled programs) is
    byte-identical to the pre-adapter engine; the bank adds exactly the
    per-slot id vector (composing with speculation's history leaves)."""
    model, params = model_params
    plain = ServeEngine(model, params, n_slots=2)
    assert set(plain._state) == {"cache", "last_tok", "keys", "remaining"}
    assert plain.adapter_stats() == {"adapters": 0}
    bank = _lora_bank(model)
    tenants = ServeEngine(model, params, n_slots=2, adapter_bank=bank)
    assert set(tenants._state) == {
        "cache", "last_tok", "keys", "remaining", "adapter_ids",
    }
    assert tenants._state["adapter_ids"].dtype == jnp.int32
    both = ServeEngine(
        model, params, n_slots=2, adapter_bank=bank, speculative_k=2
    )
    assert set(both._state) == {
        "cache", "last_tok", "keys", "remaining", "hist", "hist_len",
        "adapter_ids",
    }
    stats = tenants.adapter_stats()
    assert stats["adapters"] == 1 and stats["adapters_registered"] == 2


def test_adapter_prefix_keys_are_tenant_scoped(model_params):
    """Two tenants sharing a prompt must NOT splice from each other's
    cache (their KV segments embed different weights); the same tenant
    re-running the prompt must. Tokens stay per-tenant deterministic."""
    model, params = model_params
    # factors large enough that each tenant's greedy stream leaves the
    # base model's with a margin (at 0.05 a toy-model near-tie can hide
    # a live delta)
    bank = _lora_bank(model, scale=0.2)
    engine = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=8, adapter_bank=bank,
        prefix_cache_bytes=16 * 1024 * 1024,
    )
    prompt = _prompt(2400, 12)

    def run(aid):
        rid = engine.submit(
            Request(prompt=prompt, max_new_tokens=6, adapter=aid)
        )
        return {c.request_id: c for c in engine.run_until_idle()}[rid].tokens

    base, t1 = run(0), run(1)
    assert engine.n_splices == 0  # tenant 1 never reuses tenant 0's cache
    t2 = run(2)
    assert engine.n_splices == 0  # nor tenant 2 either of them
    assert run(1) == t1 and engine.n_splices == 1  # same-tenant re-run does
    assert run(2) == t2 and engine.n_splices == 2
    # the deltas are live: each tenant's stream differs from base
    assert t1 != base and t2 != base and t1 != t2


def test_adapter_spec_and_splice_composed(model_params):
    """Adapters x speculation x prefix splices: the three per-slot
    mechanisms share the slot state and must stay invisible composed —
    byte-identical to the plain adapter engine on the same stream."""
    model, params = model_params
    bank = _lora_bank(model)
    shared = [7, 8, 9, 10, 11] * 2
    reqs = [(shared + [20 + i], 8 + (i % 3), i % 3) for i in range(6)]

    def run(**kwargs):
        engine = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8,
            adapter_bank=bank, **kwargs,
        )
        ids = [
            engine.submit(Request(prompt=p, max_new_tokens=m, adapter=a))
            for p, m, a in reqs
        ]
        done = {c.request_id: c for c in engine.run_until_idle()}
        return engine, [done[rid].tokens for rid in ids]

    _, plain = run()
    engine, composed = run(
        speculative_k=2, prefix_cache_bytes=16 * 1024 * 1024
    )
    assert composed == plain
    assert engine.n_splices >= 1  # both mechanisms measurably fired
    assert engine.spec_stats()["spec_steps_consumed"] > 0


def test_adapter_refresh_picks_up_registrations(model_params):
    """register/evict after engine construction are live at the NEXT
    ``step()``: the engine notices the bank's version moved and
    re-merges automatically (no ``refresh_adapters()`` call needed —
    before this, submit admitted the new id while serving silently ran
    the stale zero-factor merge), matching an engine built fresh over
    the same bank. The eager path stays available and idempotent."""
    model, params = model_params
    bank = _lora_bank(model, tenants=(1,))
    engine = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=8, adapter_bank=bank
    )
    prompt = _prompt(2500, 6)

    def run(eng, aid):
        rid = eng.submit(
            Request(prompt=prompt, max_new_tokens=6, adapter=aid)
        )
        return {c.request_id: c for c in eng.run_until_idle()}[rid].tokens

    import numpy as np

    base = run(engine, 0)
    rng = np.random.Generator(np.random.PCG64(77))
    bank.register("late", jax.tree_util.tree_map(
        lambda leaf: jnp.asarray(
            # 0.2, not 0.05: a margin over the toy model's near-ties
            rng.standard_normal(leaf.shape) * 0.2, leaf.dtype
        ),
        bank.row_zeros(),
    ))
    fresh = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=8, adapter_bank=bank
    )
    got = run(engine, 2)  # no refresh_adapters(): step() re-merged
    assert got == run(fresh, 2) and got != base
    engine.refresh_adapters()  # eager path: idempotent no-op here
    assert run(engine, 2) == got
    plain = ServeEngine(model, params, n_slots=1)
    with pytest.raises(ValueError):
        plain.refresh_adapters()


def test_adapter_row_reuse_never_splices_stale_kv(model_params):
    """The row-recycling hazard: evict A, register B — the lowest-free
    policy hands B the exact row A held, but A's prefix segments were
    computed with A's factors. Generation-scoped prefix keys make B's
    lookups miss them structurally (and B's own re-runs still hit)."""
    import numpy as np

    model, params = model_params
    bank = _lora_bank(model, tenants=(1,))
    engine = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=8, adapter_bank=bank,
        prefix_cache_bytes=16 * 1024 * 1024,
    )
    prompt = _prompt(2600, 12)

    def run(aid):
        rid = engine.submit(
            Request(prompt=prompt, max_new_tokens=6, adapter=aid)
        )
        return {c.request_id: c for c in engine.run_until_idle()}[rid].tokens

    t_a = run(1)
    assert run(1) == t_a and engine.n_splices == 1  # A's cache is hot
    bank.evict("tenant-1")
    rng = np.random.Generator(np.random.PCG64(555))
    row = bank.register("tenant-B", jax.tree_util.tree_map(
        lambda leaf: jnp.asarray(
            rng.standard_normal(leaf.shape) * 0.05, leaf.dtype
        ),
        bank.row_zeros(),
    ))
    assert row == 1  # B really did recycle A's row
    t_b = run(1)
    # B's first run must NOT splice from A's stale segments...
    assert engine.n_splices == 1
    assert t_b != t_a  # ...and B's factors are live, not A's
    # ...while B's own segments are reachable on the re-run
    assert run(1) == t_b and engine.n_splices == 2


def test_adapter_evicted_while_queued(model_params):
    """A request admitted under a live tenant whose row is evicted (or
    recycled to a new tenant) before refill completes as
    ``adapter_evicted`` — zero tokens, zero device work — never decoding
    under zeroed or another tenant's factors."""
    import numpy as np

    model, params = model_params
    bank = _lora_bank(model, tenants=(1,))
    engine = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=8, adapter_bank=bank
    )
    rid = engine.submit(
        Request(prompt=_prompt(2700, 5), max_new_tokens=6, adapter=1)
    )
    bank.evict("tenant-1")
    rng = np.random.Generator(np.random.PCG64(556))
    bank.register("usurper", jax.tree_util.tree_map(  # recycles row 1
        lambda leaf: jnp.asarray(
            rng.standard_normal(leaf.shape) * 0.05, leaf.dtype
        ),
        bank.row_zeros(),
    ))
    (done,) = engine.run_until_idle()
    assert done.request_id == rid
    assert done.finish_reason == "adapter_evicted" and done.tokens == []
    assert engine.n_prefills == 0 and engine.n_chains == 0
    assert engine.adapter_stats()["adapter_rejected"] == 1
    # a fresh submit under the recycled row is the NEW tenant's traffic
    rid2 = engine.submit(
        Request(prompt=_prompt(2700, 5), max_new_tokens=6, adapter=1)
    )
    (done2,) = engine.run_until_idle()
    assert done2.request_id == rid2 and done2.finish_reason != "adapter_evicted"
    assert len(done2.tokens) == 6


# ------------------------------------------------- robustness (ISSUE 9)

def test_robustness_on_no_faults_token_exact(model_params):
    """The acceptance pin: guard_nonfinite + a generous deadline with NO
    faults is invisible — per-request tokens byte-identical to the plain
    engine and to one-shot generate(), zero extra compiles (the finite
    flag is a scan output of the SAME chain program, never a new
    trace)."""
    model, params = model_params
    reqs = [(_prompt(3000 + i, 4 + 3 * i), 6 + 2 * i) for i in range(4)]

    def run(**kwargs):
        engine = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=4, **kwargs
        )
        for p, n in reqs:
            engine.submit(Request(prompt=p, max_new_tokens=n))
        done = {c.request_id: c for c in engine.run_until_idle()}
        return engine, done

    plain_eng, plain = run()
    guard_eng, guarded = run(guard_nonfinite=True, default_deadline_s=300.0)
    assert plain.keys() == guarded.keys()
    for rid in plain:
        assert guarded[rid].tokens == plain[rid].tokens
        assert guarded[rid].finish_reason == plain[rid].finish_reason
    for (p, n), rid in zip(reqs, sorted(plain)):
        assert guarded[rid].tokens == _reference(model, params, p, n)
    # same number of compiled programs as the plain engine
    assert (guard_eng._chain._cache_size()
            == plain_eng._chain._cache_size() == 1)
    assert (guard_eng._prefill._cache_size()
            == plain_eng._prefill._cache_size())
    stats = guard_eng.fault_stats()
    assert stats["guard_nonfinite"] == 1 and stats["chaos"] == 0
    assert stats["nonfinite_quarantined"] == 0
    assert stats["deadline_expired"] == 0 and stats["cancelled"] == 0


def test_robustness_fetch_budget(model_params, monkeypatch):
    """guard + deadline + cancel sweeps cost ZERO extra fetches: the
    finite flags ride the chain's one batched fetch, the sweep is pure
    host bookkeeping — budget stays chains + prefills + splices."""
    model, params = model_params
    prompts = [_prompt(3100 + i, 5 + 2 * i) for i in range(4)]
    calls = {"n": 0}
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get",
        lambda x: (calls.__setitem__("n", calls["n"] + 1), real_get(x))[1],
    )
    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=4,
        guard_nonfinite=True, default_deadline_s=300.0,
    )
    rids = [
        engine.submit(Request(prompt=p, max_new_tokens=10))
        for p in prompts
    ]
    engine.cancel(rids[-1])  # queued cancel: completes with zero fetches
    done = engine.run_until_idle()
    assert len(done) == 4
    assert calls["n"] == engine.n_chains + engine.n_prefills


def test_nonfinite_quarantine_isolates_slot(model_params):
    """An injected NaN logits row poisons exactly one slot: that request
    completes ``"nonfinite"`` with a strict prefix of its clean tokens,
    while the co-scheduled slot's request stays byte-identical to a
    chaos-free run — the fault never crosses the slot boundary."""
    from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

    model, params = model_params
    reqs = [(_prompt(3200, 5), 12), (_prompt(3201, 8), 12)]

    def run(chaos=None):
        engine = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=4,
            guard_nonfinite=True, chaos=chaos,
        )
        for p, n in reqs:
            engine.submit(Request(prompt=p, max_new_tokens=n))
        return engine, {c.request_id: c for c in engine.run_until_idle()}

    _, clean = run()
    # poison slot 0 (request 0, FIFO refill) at global decode step 2
    engine, faulty = run(ChaosConfig(nan_logit_slot=0, nan_logit_step=2))
    assert faulty[0].finish_reason == "nonfinite"
    assert 0 < len(faulty[0].tokens) < len(clean[0].tokens)
    assert faulty[0].tokens == clean[0].tokens[: len(faulty[0].tokens)]
    # the co-scheduled slot never sees the fault
    assert faulty[1].tokens == clean[1].tokens
    assert faulty[1].finish_reason == clean[1].finish_reason == "length"
    stats = engine.fault_stats()
    assert stats["nonfinite_quarantined"] == 1 and stats["chaos"] == 1


def test_deadline_queued_and_active(model_params):
    """Deadlines fire at both boundaries: a queued request whose budget
    expired completes ``"deadline"`` at refill with zero device work; an
    ACTIVE request caught by an (injected) launch stall completes at the
    next chain boundary keeping the tokens it already earned."""
    from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

    model, params = model_params
    # queued expiry: the deadline is tiny, refill sees it already dead
    engine = ServeEngine(model, params, n_slots=1, tokens_per_launch=4)
    rid = engine.submit(Request(
        prompt=_prompt(3300, 5), max_new_tokens=6, deadline_s=1e-6,
    ))
    (done,) = engine.run_until_idle()
    assert done.request_id == rid
    assert done.finish_reason == "deadline" and done.tokens == []
    assert engine.n_prefills == 0 and engine.n_chains == 0
    assert engine.fault_stats()["deadline_expired"] == 1

    # active expiry: chain 1 stalls past the deadline; the sweep at the
    # next boundary completes the request with its pre-stall tokens
    engine = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=4,
        chaos=ChaosConfig(stall_chain=1, stall_s=0.3),
    )
    rid = engine.submit(Request(
        prompt=_prompt(3301, 5), max_new_tokens=12, deadline_s=0.25,
    ))
    (done,) = engine.run_until_idle()
    assert done.request_id == rid
    assert done.finish_reason == "deadline"
    assert 0 < len(done.tokens) < 12  # partial progress kept
    assert engine.fault_stats()["deadline_expired"] == 1


def test_cancel_queued_and_active(model_params):
    """Host-side cancel: a queued request completes ``"cancelled"`` with
    zero tokens at refill; an active one at the next chain boundary with
    its partial tokens; an unknown/finished id returns False."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=4)
    r0 = engine.submit(Request(prompt=_prompt(3400, 5), max_new_tokens=16))
    r1 = engine.submit(Request(prompt=_prompt(3401, 5), max_new_tokens=6))
    assert engine.cancel(r1) is True  # still queued
    assert engine.cancel(999) is False  # unknown id
    first = engine.step()  # prefill r0 + one chain; r1 dies at refill
    cancelled = [c for c in first if c.request_id == r1]
    assert cancelled and cancelled[0].finish_reason == "cancelled"
    assert cancelled[0].tokens == []
    assert engine.cancel(r0) is True  # active now: boundary cancel
    done = {c.request_id: c for c in engine.run_until_idle()}
    assert done[r0].finish_reason == "cancelled"
    assert 0 < len(done[r0].tokens) < 16  # earned tokens kept
    assert engine.cancel(r0) is False  # already finished
    assert engine.fault_stats()["cancelled"] == 2


def test_close_and_drain(model_params):
    """Graceful shutdown: close() turns submit into QueueClosed
    backpressure, drain() runs every accepted request to completion —
    no accepted request is ever dropped."""
    from pytorch_distributed_training_tutorials_tpu.serve import QueueClosed

    model, params = model_params
    engine = ServeEngine(model, params, n_slots=1, tokens_per_launch=4)
    rids = [
        engine.submit(Request(prompt=_prompt(3500 + i, 4), max_new_tokens=5))
        for i in range(3)
    ]
    done = engine.drain()
    assert engine.closed
    assert sorted(c.request_id for c in done) == rids
    assert all(len(c.tokens) == 5 for c in done)
    with pytest.raises(QueueClosed):
        engine.submit(Request(prompt=_prompt(3510, 4), max_new_tokens=5))
    assert engine.idle


def test_prefill_error_isolated(model_params):
    """A prefill that raises is that REQUEST's failure, not the
    engine's: it completes ``"error"`` with zero tokens and the engine
    keeps serving everyone else token-exactly."""
    from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

    model, params = model_params
    reqs = [(_prompt(3600 + i, 5), 6) for i in range(3)]
    plain = ServeEngine(model, params, n_slots=1, tokens_per_launch=4)
    for p, n in reqs:
        plain.submit(Request(prompt=p, max_new_tokens=n))
    clean = {c.request_id: c for c in plain.run_until_idle()}

    engine = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=4,
        chaos=ChaosConfig(fail_prefill_request=1),
    )
    for p, n in reqs:
        engine.submit(Request(prompt=p, max_new_tokens=n))
    done = {c.request_id: c for c in engine.run_until_idle()}
    assert done[1].finish_reason == "error" and done[1].tokens == []
    for rid in (0, 2):
        assert done[rid].tokens == clean[rid].tokens
        assert done[rid].finish_reason == "length"
    assert engine.fault_stats()["prefill_errors"] == 1


def test_spec_guard_quarantine_composed(model_params):
    """The guard composes with speculation: the poisoned slot
    quarantines out of the (S, T, k+1) verify block while the
    co-scheduled slot stays byte-identical to the clean spec run."""
    from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

    model, params = model_params
    reqs = [(_prompt(3700, 5), 12), (_prompt(3701, 8), 12)]

    def run(chaos=None):
        engine = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=4,
            speculative_k=2, guard_nonfinite=True, chaos=chaos,
        )
        for p, n in reqs:
            engine.submit(Request(prompt=p, max_new_tokens=n))
        return engine, {c.request_id: c for c in engine.run_until_idle()}

    _, clean = run()
    engine, faulty = run(ChaosConfig(nan_logit_slot=0, nan_logit_step=2))
    assert faulty[0].finish_reason == "nonfinite"
    assert faulty[0].tokens == clean[0].tokens[: len(faulty[0].tokens)]
    assert faulty[1].tokens == clean[1].tokens
    assert engine.fault_stats()["nonfinite_quarantined"] == 1


def test_robustness_off_state_is_unchanged(model_params):
    """guard/deadline/chaos OFF keeps the slot-state tree (and so the
    compiled programs) byte-identical to the pre-robustness engine —
    and even guard ON adds NO state leaves (the finite flag is a chain
    output, not carried state)."""
    model, params = model_params
    base_keys = {"cache", "last_tok", "keys", "remaining"}
    assert set(ServeEngine(model, params, n_slots=2)._state) == base_keys
    guarded = ServeEngine(
        model, params, n_slots=2, guard_nonfinite=True,
        default_deadline_s=60.0,
    )
    assert set(guarded._state) == base_keys


def test_robustness_validation(model_params):
    """Bad lifecycle params bounce synchronously at construction/submit."""
    model, params = model_params
    with pytest.raises(ValueError):
        ServeEngine(model, params, default_deadline_s=0.0)
    with pytest.raises(ValueError):
        ServeEngine(model, params, default_deadline_s=-1.0)
    engine = ServeEngine(model, params, n_slots=1)
    with pytest.raises(ValueError):
        engine.submit(Request(
            prompt=[1, 2], max_new_tokens=2, deadline_s=0.0,
        ))
    assert engine.idle


# ---------------------------------------------- flight recorder (ISSUE 10)

def _flight_engine(model, params, **kw):
    from pytorch_distributed_training_tutorials_tpu.obs.flight import FlightRecorder

    rec = FlightRecorder(capacity=256, **{
        k: kw.pop(k) for k in ("dump_path",) if k in kw
    })
    return rec, ServeEngine(
        model, params, n_slots=2, tokens_per_launch=4, flight=rec, **kw
    )


def test_flight_records_full_request_lifecycle(model_params):
    """Every completed request on a recorder-on engine gets a FULL span
    (submit -> queue_pop -> prefill -> complete), the event counts
    reconcile with the engine's own counters, and the recorded
    latency/TTFT are the engine's Completion numbers verbatim — so the
    histogram percentiles are sample-identical to sorting the list."""
    model, params = model_params
    rec, engine = _flight_engine(model, params)
    prompts = [_prompt(5000 + i, 4 + 2 * i) for i in range(4)]
    for p in prompts:
        engine.submit(Request(prompt=p, max_new_tokens=8))
    completions = {c.request_id: c for c in engine.run_until_idle()}
    assert len(rec.done_spans) == len(prompts) and not rec.spans
    for span in rec.done_spans:
        assert {"submit_t", "queue_pop_t", "prefill_t", "complete_t",
                "finish_reason", "slot"} <= set(span)
        comp = completions[span["rid"]]
        assert span["e2e_s"] == pytest.approx(comp.latency_s, abs=1e-5)
        assert span["ttft_s"] == pytest.approx(comp.ttft_s, abs=1e-5)
        assert span["tokens"] == len(comp.tokens)
    kc = rec.kind_counts
    assert kc["submit"] == kc["queue_pop"] == kc["complete"] == 4
    assert kc["prefill"] == engine.n_prefills
    assert kc["chain_start"] == kc["chain_end"] == engine.n_chains
    assert rec.hist["e2e"].n == rec.hist["ttft"].n == 4
    assert rec.hist["chain_util"].n == engine.n_chains
    # the receipt surface rides the unified stats() aggregate
    stats = engine.stats()
    assert stats["flight"] == 1 and stats["flight_spans_done"] == 4
    assert stats["e2e_count"] == 4 and stats["ttft_p95_s"] > 0
    assert engine.flight_stats() == rec.summary()


def test_flight_fetch_budget_unchanged(model_params, monkeypatch):
    """Stamping events is host bookkeeping: with the recorder ON the
    monkeypatched jax.device_get count stays EXACTLY chains + prefills —
    the recorder never buys observability with a sync."""
    model, params = model_params
    rec, engine = _flight_engine(model, params)
    prompts = [_prompt(5100 + i, 5) for i in range(3)]  # before the spy
    calls = {"n": 0}
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get",
        lambda x: (calls.__setitem__("n", calls["n"] + 1), real_get(x))[1],
    )
    for p in prompts:
        engine.submit(Request(prompt=p, max_new_tokens=10))
    assert len(engine.run_until_idle()) == 3
    assert calls["n"] == engine.n_chains + engine.n_prefills
    assert rec.n_events > 0  # the recorder was live the whole time


def test_flight_off_engine_unchanged(model_params):
    """Recorder OFF (the default) keeps the slot-state tree byte-
    identical and compiles the same number of programs; recorder ON
    changes neither — only host-side bookkeeping differs, so the token
    streams match bitwise."""
    model, params = model_params
    base_keys = {"cache", "last_tok", "keys", "remaining"}

    def run(flight=None):
        engine = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=4, flight=flight,
        )
        for i in range(3):
            engine.submit(
                Request(prompt=_prompt(5200 + i, 6), max_new_tokens=8)
            )
        toks = [c.tokens for c in engine.run_until_idle()]
        return engine, toks

    off_eng, off_toks = run()
    from pytorch_distributed_training_tutorials_tpu.obs.flight import FlightRecorder

    on_eng, on_toks = run(FlightRecorder(capacity=64))
    assert set(off_eng._state) == set(on_eng._state) == base_keys
    assert on_toks == off_toks
    assert (off_eng._chain._cache_size()
            == on_eng._chain._cache_size())
    assert (off_eng._prefill._cache_size()
            == on_eng._prefill._cache_size())
    assert off_eng.flight_stats() == {"flight": 0}


def test_flight_chaos_fault_dump_names_slot(model_params, tmp_path):
    """A quarantined NaN slot auto-dumps one graft-flightlog/v1 snapshot
    whose trigger names the (slot, chain step) — the acceptance
    criterion for the post-mortem path."""
    from pytorch_distributed_training_tutorials_tpu.obs.flight import load_flightlog
    from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

    model, params = model_params
    dump_path = str(tmp_path / "fault.jsonl")
    rec, engine = _flight_engine(
        model, params, dump_path=dump_path,
        guard_nonfinite=True,
        chaos=ChaosConfig(nan_logit_slot=0, nan_logit_step=2),
    )
    for i in range(2):
        engine.submit(Request(prompt=_prompt(5300 + i, 5), max_new_tokens=10))
    done = {c.request_id: c for c in engine.run_until_idle()}
    assert done[0].finish_reason == "nonfinite"
    snaps = load_flightlog(dump_path)
    assert len(snaps) == 1 and rec.n_faults == 1
    trig = snaps[0]["trigger"]
    assert trig["fault_kind"] == "nonfinite" and trig["slot"] == 0
    assert trig["rid"] == 0 and "chain_step" in trig
    # the dump fires AT the fault, before completion: the poisoned
    # request is still a live span there, and closes with the fault
    # finish_reason afterwards
    assert any(s["rid"] == 0 and s.get("slot") == 0
               for s in snaps[0]["live_spans"])
    (nf_span,) = [s for s in rec.done_spans
                  if s.get("finish_reason") == "nonfinite"]
    assert nf_span["rid"] == 0


def test_engine_stats_parts_filter(model_params):
    """stats() unifies the per-feature dicts; the parts filter lets
    multi-engine callers avoid clobbering (an engine with no prefix
    cache reports prefix_cache=0 — merging that over a cache-on
    engine's dict would lie)."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=1)
    s = engine.stats()
    for key in ("prefix_cache", "speculative", "adapters", "chaos",
                "flight"):
        assert key in s
    assert engine.stats("fault") == engine.fault_stats()
    assert engine.stats("flight") == {"flight": 0}
    only = engine.stats("spec", "adapters")
    assert "prefix_cache" not in only and "speculative" in only
    with pytest.raises(ValueError):
        engine.stats("nonsense")


# ------------------------------------------------------------- the selftest

@pytest.mark.slow
def test_serve_selftest_subprocess(tmp_path):
    """``python -m ...serve --selftest`` — the end-to-end continuous-
    batching smoke (token-exactness vs generate() included) — succeeds on
    the forced 8-device CPU mesh."""
    from pytorch_distributed_training_tutorials_tpu.obs import load_receipt, validate_receipt

    json_path = str(tmp_path / "selftest.json")
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tutorials_tpu.serve", "--selftest",
         "--json", json_path],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=os.environ.copy(),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    receipt = json.loads(out.stdout.strip().splitlines()[-1])
    assert receipt["ok"] is True, receipt.get("problems")
    assert validate_receipt(receipt, kind="serve_selftest") == []
    assert receipt["token_exact_mismatches"] == 0
    assert receipt["backpressure_seen"] is True
    # the speculative arm's mechanism receipt (the ISSUE 7 CPU-mesh
    # criterion, recorded through make_receipt): token-exact, accepted
    # length > 1, fewer sequential verify forwards than tokens emitted
    assert receipt["spec_token_exact"] is True
    assert receipt["spec_mean_accepted_len"] > 1.0
    assert receipt["n_verify_forwards"] < receipt["spec_generated_tokens"]
    # the multi-tenant arm (ISSUE 8): mixed-tenant streams byte-identical
    # to dedicated engines + the base model, admission enforced
    assert receipt["adapter_token_exact"] is True
    assert receipt["adapters"] == 1 and receipt["adapter_requests"] >= 1
    assert load_receipt(json_path)["ok"] is True


@pytest.mark.slow
def test_serve_selftest_chaos_subprocess(tmp_path):
    """``--selftest --chaos`` — the fault-injection arm (ISSUE 9): one
    quarantined slot with a co-scheduled request token-exact to the
    clean engine, a deadline expiry, a cancellation, QueueClosed after
    drain, the unchanged fetch budget, and one skipped training step —
    all counted into the receipt."""
    from pytorch_distributed_training_tutorials_tpu.obs import load_receipt, validate_receipt

    json_path = str(tmp_path / "selftest_chaos.json")
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tutorials_tpu.serve", "--selftest",
         "--chaos", "--json", json_path],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=os.environ.copy(),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    receipt = json.loads(out.stdout.strip().splitlines()[-1])
    assert receipt["ok"] is True, receipt.get("problems")
    assert validate_receipt(receipt, kind="serve_selftest") == []
    assert receipt["chaos"] == 1 and receipt["guard_nonfinite"] == 1
    assert receipt["nonfinite_quarantined"] == 1
    assert receipt["deadline_expired"] == 1
    assert receipt["cancelled"] == 1
    assert receipt["chaos_token_exact"] is True
    # budget = chains + prefills + splices, already enforced inside the
    # selftest (a violation flips ok=False); the count is informational
    assert receipt["chaos_host_fetches"] >= 1
    assert receipt["steps_skipped"] == 1
    # ISSUE 10: the quarantine auto-dumped flight snapshots and one of
    # them names the poisoned slot in its trigger
    assert receipt["chaos_flight_dumps"] >= 1
    assert receipt["chaos_flight_named_slot"] is True
    assert load_receipt(json_path)["ok"] is True


@pytest.mark.slow
def test_serve_selftest_flight_subprocess(tmp_path):
    """``--selftest --flight`` — the flight-recorder arm (ISSUE 10):
    recorder-on replay of the staggered stream is token-identical with
    the fetch budget intact, every request gets a full span, event
    counts reconcile with the engine counters, and the histogram
    p50/p95 match sort-based percentiles within the documented bucket
    bound."""
    from pytorch_distributed_training_tutorials_tpu.obs import load_receipt, validate_receipt

    json_path = str(tmp_path / "selftest_flight.json")
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tutorials_tpu.serve", "--selftest",
         "--flight", "--json", json_path],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=os.environ.copy(),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    receipt = json.loads(out.stdout.strip().splitlines()[-1])
    assert receipt["ok"] is True, receipt.get("problems")
    assert validate_receipt(receipt, kind="serve_selftest") == []
    assert receipt["flight"] == 1
    assert receipt["flight_span_full"] is True
    assert receipt["flight_events_consistent"] is True
    assert receipt["flight_hist_vs_sort"] is True
    assert receipt["flight_requests"] >= 3
    assert receipt["flight_spans_done"] == receipt["flight_requests"]
    assert receipt["e2e_count"] == receipt["flight_requests"]
    assert load_receipt(json_path)["ok"] is True


@pytest.mark.slow
def test_serve_selftest_sentry_subprocess(tmp_path):
    """``--selftest --sentry`` — the contract-sentry arm (ISSUE 19): a
    sentry-instrumented engine over the base stream shows zero steady
    recompiles, fetch accounting equal to an independent monkeypatch
    spy AND the declared budget, and zero re-uploads, token-exact to
    the bare engine; then one injected violation per probe class each
    yields exactly one typed flight event + one auto-dump naming its
    trigger."""
    from pytorch_distributed_training_tutorials_tpu.obs import load_receipt, validate_receipt

    json_path = str(tmp_path / "selftest_sentry.json")
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tutorials_tpu.serve", "--selftest",
         "--sentry", "--json", json_path],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=os.environ.copy(),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    receipt = json.loads(out.stdout.strip().splitlines()[-1])
    assert receipt["ok"] is True, receipt.get("problems")
    assert validate_receipt(receipt, kind="serve_selftest") == []
    assert receipt["sentry"] == 1
    assert receipt["sentry_token_exact"] is True
    # the clean steady leg: every contract held (the summary snapshot
    # is taken BEFORE the injected violations)
    assert receipt["sentry_steady_recompiles"] == 0
    assert receipt["sentry_fetch_budget_ok"] == 1
    assert receipt["sentry_reuploads"] == 0
    assert receipt["sentry_fetched"] == receipt["sentry_budgeted"] > 0
    # each injected violation class was caught exactly once, with one
    # graft-flightlog/v1 auto-dump per class
    assert receipt["sentry_injected_recompile_caught"] is True
    assert receipt["sentry_injected_budget_caught"] is True
    assert receipt["sentry_injected_reupload_caught"] is True
    assert receipt["sentry_dump_snapshots"] == 3
    assert load_receipt(json_path)["ok"] is True


# ------------------------------------------ request-loop pipelining (ISSUE 11)

def test_pipeline_validation():
    model, params = _make()
    with pytest.raises(ValueError, match="pipeline_depth"):
        ServeEngine(model, params, pipeline_depth=0)
    # chunk granularity must match the pow2 bucket family (floor 8) so
    # chunk shapes come from the SAME compile set as prefill buckets
    for bad in (7, 4, 12):
        with pytest.raises(ValueError, match="prefill_chunk"):
            ServeEngine(model, params, prefill_chunk=bad)


def test_pipeline_off_engine_unchanged(model_params):
    """Depth 1 / chunk 0 (the defaults) keep the slot-state tree and the
    compiled-program counts byte-identical to the pre-pipeline engine —
    the same off-path contract every serve feature holds (PR 7/8/9)."""
    model, params = model_params
    base_keys = {"cache", "last_tok", "keys", "remaining"}

    def run(**kw):
        engine = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=4, **kw
        )
        for i in range(3):
            engine.submit(
                Request(prompt=_prompt(6000 + i, 6), max_new_tokens=8)
            )
        return engine, [c.tokens for c in engine.run_until_idle()]

    default_eng, default_toks = run()
    explicit_eng, explicit_toks = run(pipeline_depth=1, prefill_chunk=0)
    assert set(default_eng._state) == set(explicit_eng._state) == base_keys
    assert explicit_toks == default_toks
    assert (default_eng._chain._cache_size()
            == explicit_eng._chain._cache_size())
    assert (default_eng._prefill._cache_size()
            == explicit_eng._prefill._cache_size())
    assert default_eng.pipeline_stats() == {
        "pipeline_depth": 1, "prefill_chunk": 0, "n_chunks": 0,
    }
    assert default_eng.stats("pipeline") == default_eng.pipeline_stats()


def test_pipeline_ordering_dispatch_before_fetch(model_params):
    """The tentpole mechanism OBSERVED, not inferred from counters: at
    depth 2 chain ``i+1`` is dispatched before chain ``i``'s result is
    fetched (the host roundtrip overlaps device execution — device
    program order still runs them back to back); the very same spy on a
    depth-1 engine shows the serial order. Every dispatched chain is
    eventually fetched, in dispatch order (including the trailing
    bubble chain the pipeline drains at end of stream)."""
    model, params = model_params
    prompt = _prompt(6100, 5)

    def run(depth):
        engine = ServeEngine(
            model, params, n_slots=1, tokens_per_launch=4,
            pipeline_depth=depth,
        )
        log, chain_ids, keep = [], {}, []
        real_chain = engine._chain

        def spy_chain(*args):
            state, out = real_chain(*args)
            keep.append(out)  # pin ids so CPython never recycles them
            chain_ids[id(out)] = len(chain_ids)
            log.append(("dispatch", chain_ids[id(out)]))
            return state, out

        engine._chain = spy_chain
        real_get = jax.device_get

        def spy_get(x):
            if id(x) in chain_ids:
                log.append(("fetch", chain_ids[id(x)]))
            return real_get(x)

        jax.device_get = spy_get
        try:
            engine.submit(Request(prompt=prompt, max_new_tokens=13))
            done = engine.run_until_idle()
        finally:
            jax.device_get = real_get
        assert len(done) == 1 and len(done[0].tokens) == 13
        return log, done[0].tokens

    serial_log, serial_toks = run(1)
    piped_log, piped_toks = run(2)
    assert piped_toks == serial_toks
    # serial: chain 0's fetch lands before chain 1 is dispatched
    assert serial_log.index(("fetch", 0)) < serial_log.index(("dispatch", 1))
    # pipelined: chain 1 is IN FLIGHT before chain 0's fetch (the win)
    assert piped_log.index(("dispatch", 1)) < piped_log.index(("fetch", 0))
    fetched = [i for op, i in piped_log if op == "fetch"]
    assert fetched == list(range(len(fetched)))  # FIFO collect, none lost
    dispatched = [i for op, i in piped_log if op == "dispatch"]
    assert dispatched == fetched  # every chain collected exactly once


@pytest.mark.parametrize(
    "cfg_kwargs",
    [
        dict(),
        # the scan/GQA variants ride the slow tier (tier-1 time budget,
        # ISSUE 11): the unrolled arm pins generate()-exactness and the
        # int8 arm pins the quantized engine-vs-engine contract; the
        # cheaper *_variant_layouts tests keep per-layout coverage fast
        pytest.param(dict(scan_layers=True), marks=pytest.mark.slow),
        pytest.param(dict(n_kv_heads=2), marks=pytest.mark.slow),
        dict(kv_cache_dtype=jnp.int8),
    ],
    ids=["unrolled", "scan_layers", "gqa", "int8_kv"],
)
def test_pipeline_depth2_token_exact_layouts(cfg_kwargs):
    """The ISSUE 11 acceptance pin: a depth-2 + chunked-prefill stream
    composed with prefix splices AND speculation is byte-identical
    greedy to the depth-1 engine under the same chunk settings on every
    cache layout (both arms chunked, so the comparison stays bitwise on
    int8-KV where the chunked continuation reassociates quantization),
    and to one-shot generate() on the full-precision layouts."""
    import dataclasses

    cfg = dataclasses.replace(CFG, **cfg_kwargs)
    model, params = _make(cfg)
    reqs = _overlap_stream(0.7, n_requests=6) + [(_prompt(6200, 20), 6)]
    kw = dict(prefill_chunk=8, speculative_k=2,
              prefix_cache_bytes=16 * 1024 * 1024)
    eng1, out1 = _run_stream(model, params, reqs, pipeline_depth=1, **kw)
    eng2, out2 = _run_stream(model, params, reqs, pipeline_depth=2, **kw)
    assert [c.tokens for c in out2] == [c.tokens for c in out1]
    assert eng2.n_chunks > 0  # the 14/20-token prompts streamed in chunks
    # every request still produced its first token through exactly one
    # budgeted prefill-or-splice, chunked or not
    assert eng2.n_prefills + eng2.n_splices == len(reqs)
    if "kv_cache_dtype" not in cfg_kwargs:
        for (prompt, max_new), c in zip(reqs, out2):
            assert c.tokens == _reference(model, params, prompt, max_new)


def test_chunked_prefill_token_exact_vs_unchunked(model_params):
    """Chunk-on output is byte-identical to chunk-off and generate():
    the chunked decode continuation is bitwise a whole prefill for
    full-precision caches (tests/test_transformer.py pins the kernel
    fact; this pins the engine plumbing stacked on top)."""
    model, params = model_params
    reqs = [(_prompt(6400 + i, p), m)
            for i, (p, m) in enumerate([(20, 6), (9, 8), (33, 10), (4, 5)])]
    eng_off, out_off = _run_stream(model, params, reqs)
    eng_on, out_on = _run_stream(model, params, reqs, prefill_chunk=8)
    assert [c.tokens for c in out_on] == [c.tokens for c in out_off]
    for (prompt, max_new), c in zip(reqs, out_on):
        assert c.tokens == _reference(model, params, prompt, max_new)
    # mechanism: 20 -> 8+8+4, 33 -> 8*4+1, 9 -> 8+1; the 4-token prompt
    # takes the plain prefill path untouched
    assert eng_on.n_chunks == 10
    assert eng_off.n_chunks == 0
    # the final chunk carries the request's ONE budgeted fetch, so the
    # prefill counter is conserved
    assert eng_on.n_prefills == eng_off.n_prefills == len(reqs)


def test_chunked_prefill_keeps_short_requests_flowing(model_params):
    """The fairness pin: a short request co-scheduled next to a LONG
    prompt completes within K = 2 scheduling rounds of where it lands
    when the long prompt prefills whole — chunking bounds per-round
    prefill work instead of monopolizing the loop — with identical
    tokens for both requests."""
    model, params = model_params
    long_p, short_p = _prompt(6500, 48), _prompt(6501, 4)

    def run(chunk):
        engine = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8,
            prefill_chunk=chunk,
        )
        r_long = engine.submit(Request(prompt=long_p, max_new_tokens=8))
        r_short = engine.submit(Request(prompt=short_p, max_new_tokens=8))
        rounds, short_round, out = 0, None, {}
        while not engine.idle:
            rounds += 1
            for c in engine.step():
                out[c.request_id] = c
                if c.request_id == r_short and short_round is None:
                    short_round = rounds
        return engine, out[r_short], out[r_long], short_round

    eng0, short0, long0, round0 = run(0)
    eng1, short1, long1, round1 = run(16)
    assert short1.tokens == short0.tokens
    assert long1.tokens == long0.tokens
    assert short1.tokens == _reference(model, params, short_p, 8)
    assert eng1.n_chunks == 3  # 48 tokens at 16/chunk: 16+16+final 16
    assert round1 <= round0 + 2


def test_pipeline_cancel_and_deadline_at_observed_boundary(model_params):
    """Lifecycle enforcement under depth 2 fires at the OBSERVED chain
    boundary (host bookkeeping runs one chain behind the device):
    cancel keeps the tokens already fetched, the still-in-flight
    chain's rows for that slot are dropped on the floor, and the
    co-scheduled request never notices."""
    model, params = model_params
    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=4, pipeline_depth=2,
    )
    p0, p1 = _prompt(6600, 5), _prompt(6601, 5)
    r0 = engine.submit(Request(prompt=p0, max_new_tokens=16))
    r1 = engine.submit(Request(prompt=p1, max_new_tokens=16))
    engine.step()  # dispatch chain 0 (nothing observed yet)
    engine.step()  # dispatch chain 1, observe chain 0
    assert engine.cancel(r0) is True
    done = {c.request_id: c for c in engine.run_until_idle()}
    assert done[r0].finish_reason == "cancelled"
    assert 0 < len(done[r0].tokens) < 16  # observed tokens kept
    ref0 = _reference(model, params, p0, 16)
    assert done[r0].tokens == ref0[: len(done[r0].tokens)]
    assert done[r1].finish_reason == "length"
    assert done[r1].tokens == _reference(model, params, p1, 16)

    # a queued request's deadline dies at refill: zero chains, zero
    # chunks, zero device work — even with chunking configured
    engine2 = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=4, pipeline_depth=2,
        prefill_chunk=8,
    )
    engine2.submit(Request(
        prompt=_prompt(6602, 20), max_new_tokens=6, deadline_s=1e-6,
    ))
    (d,) = engine2.run_until_idle()
    assert d.finish_reason == "deadline" and d.tokens == []
    assert engine2.n_chains == 0 and engine2.n_chunks == 0

    # cancel landing MID-chunked-prefill abandons the pending side
    # cache before the request ever owns a budgeted prefill
    engine3 = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=4, prefill_chunk=8,
    )
    r3 = engine3.submit(Request(prompt=_prompt(6603, 30), max_new_tokens=6))
    engine3.step()  # first chunk dispatched; request now pending
    assert engine3.n_chunks >= 1 and engine3.n_prefills == 0
    assert engine3.cancel(r3) is True
    (d3,) = engine3.run_until_idle()
    assert d3.finish_reason == "cancelled" and d3.tokens == []
    assert engine3.n_prefills == 0  # the final chunk never ran


def test_pipeline_adapter_composed(model_params):
    """Multi-tenant streams survive the pipeline: depth 2 + chunked
    prefill over a mixed-tenant stream with shared prompt families is
    byte-identical to the serial engine — adapter ids ride the slot
    state and tenant-scoped prefix keys exactly as before."""
    model, params = model_params
    bank = _lora_bank(model)
    shared = _prompt(6300, 14)
    reqs = [(shared + _prompt(6301 + i, 6), 6 + (i % 3), i % 3)
            for i in range(6)]

    def run(depth):
        engine = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8,
            adapter_bank=bank, pipeline_depth=depth, prefill_chunk=8,
            prefix_cache_bytes=16 * 1024 * 1024,
        )
        ids = [
            engine.submit(Request(prompt=p, max_new_tokens=m, adapter=a))
            for p, m, a in reqs
        ]
        done = {c.request_id: c for c in engine.run_until_idle()}
        return engine, [done[rid].tokens for rid in ids]

    eng1, toks1 = run(1)
    eng2, toks2 = run(2)
    assert toks2 == toks1
    assert eng2.n_chunks > 0  # 20-token prompts chunked per tenant miss
    assert eng2.adapter_stats()["adapter_requests"] == 4  # ids 1 and 2


def test_pipeline_fetch_budget(model_params):
    """Depth 2 + chunked prefill keep the budget EXACTLY chains +
    prefills + splices: mid chunks are pure async dispatch (no fetch),
    the trailing bubble chain at end of stream is a counted chain, and
    the flight recorder adds nothing — its chain_overlap histogram
    samples every chain, trailing bubble included."""
    from pytorch_distributed_training_tutorials_tpu.obs.flight import FlightRecorder

    model, params = model_params
    reqs = _overlap_stream(0.7, n_requests=6) + [(_prompt(6700, 24), 6)]
    for rec in (None, FlightRecorder(capacity=256)):
        calls = {"n": 0}
        real_get = jax.device_get

        def counting(x, _real=real_get):
            calls["n"] += 1
            return _real(x)

        jax.device_get = counting
        try:
            engine, out = _run_stream(
                model, params, reqs, pipeline_depth=2, prefill_chunk=8,
                prefix_cache_bytes=16 * 1024 * 1024, flight=rec,
            )
        finally:
            jax.device_get = real_get
        assert len(out) == len(reqs) and engine.n_chunks > 0
        assert calls["n"] == (
            engine.n_chains + engine.n_prefills + engine.n_splices
        )
        if rec is not None:
            assert rec.hist["chain_overlap"].n == engine.n_chains


def test_serve_selftest_pipeline_subprocess(tmp_path):
    """``--selftest --pipeline`` — the ISSUE 11 arm: a depth-2 +
    chunked-prefill replay of the staggered stream is token-identical
    to the serial arm with the fetch budget intact and chunking
    visibly fired, all counted into the receipt."""
    from pytorch_distributed_training_tutorials_tpu.obs import load_receipt, validate_receipt

    json_path = str(tmp_path / "selftest_pipeline.json")
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tutorials_tpu.serve", "--selftest",
         "--pipeline", "--json", json_path],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=os.environ.copy(),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    receipt = json.loads(out.stdout.strip().splitlines()[-1])
    assert receipt["ok"] is True, receipt.get("problems")
    assert validate_receipt(receipt, kind="serve_selftest") == []
    assert receipt["pipeline_token_exact"] is True
    assert receipt["pipeline_depth"] == 2
    assert receipt["prefill_chunk"] == 8
    assert receipt["n_chunks"] >= 1
    assert receipt["pipeline_requests"] >= 3
    assert receipt["pipeline_host_fetches"] >= 1
    assert load_receipt(json_path)["ok"] is True


# ---------------------------------------------- fleet router (ISSUE 12)

def _tree_identical(a, b):
    """Byte-identical pytrees: same structure, dtypes, shapes, values."""
    la, sa = jax.tree_util.tree_flatten(a)
    lb, sb = jax.tree_util.tree_flatten(b)
    return sa == sb and all(
        x.dtype == y.dtype and x.shape == y.shape and bool((x == y).all())
        for x, y in zip(la, lb)
    )


def test_fleet_router_n1_transparency(model_params):
    """The router-off parity pin at the fleet level: ``FleetRouter``
    over ONE real engine is a transparent wrapper — byte-identical
    completions AND slot-state trees AND compiled-program counts vs
    driving the same engine directly, with the fetch budget unchanged
    (the router adds pure host bookkeeping, zero device work)."""
    from pytorch_distributed_training_tutorials_tpu.serve import FleetRouter

    model, params = model_params
    reqs = [(_prompt(7000 + i, p), m)
            for i, (p, m) in enumerate([(5, 8), (9, 6), (13, 10)])]

    def run(routed):
        engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=4)
        front = FleetRouter([engine]) if routed else engine
        calls = {"n": 0}
        real_get = jax.device_get

        def counting(x):
            calls["n"] += 1
            return real_get(x)

        jax.device_get = counting
        try:
            ids = [front.submit(Request(prompt=p, max_new_tokens=m, seed=i))
                   for i, (p, m) in enumerate(reqs)]
            done = {c.request_id: c for c in front.run_until_idle()}
        finally:
            jax.device_get = real_get
        return engine, front, [done[i] for i in ids], calls["n"]

    eng_d, _, out_d, fetches_d = run(False)
    eng_r, fr, out_r, fetches_r = run(True)
    assert [c.tokens for c in out_r] == [c.tokens for c in out_d]
    assert [c.finish_reason for c in out_r] == [
        c.finish_reason for c in out_d
    ]
    for (p, m), c in zip(reqs, out_r):
        assert c.tokens == _reference(model, params, p, m)
    assert _tree_identical(eng_r._state, eng_d._state)
    assert eng_r._chain._cache_size() == eng_d._chain._cache_size()
    assert eng_r._prefill._cache_size() == eng_d._prefill._cache_size()
    assert fetches_r == fetches_d
    assert fetches_r == eng_r.n_chains + eng_r.n_prefills + eng_r.n_splices
    assert fr.ledger.verify() == []
    stats = fr.router_stats()
    assert stats["n_replicas"] == 1
    assert stats["redispatched"] == 0 and stats["hedged"] == 0
    assert fr.replica_states() == ["healthy"]


@pytest.mark.slow
def test_fleet_router_composed_prefix_tenants_pipeline(model_params):
    """A 2-replica fleet where each replica runs the FULL serving stack
    (prefix cache + adapter bank + depth-2 pipeline + chunked prefill)
    serves a mixed-tenant shared-prefix stream token-exact to one
    identically-configured engine; the summed per-replica fetch budget
    stays exactly chains + prefills + splices, and the ledger verifies
    exactly-once delivery.

    Slow-marked under the tier-1 time-budget policy (ROADMAP): this is
    the everything-composed heavyweight; its component contracts stay
    in the fast tier via the N=1 transparency and chaos-kill tests."""
    from pytorch_distributed_training_tutorials_tpu.serve import FleetRouter

    model, params = model_params
    shared = _prompt(7100, 12)
    reqs = [(shared + _prompt(7101 + i, 5), 5 + (i % 3), i % 3)
            for i in range(6)]
    kw = dict(
        n_slots=2, tokens_per_launch=8, pipeline_depth=2, prefill_chunk=8,
        prefix_cache_bytes=16 * 1024 * 1024,
    )

    def make_engine():
        return ServeEngine(model, params, adapter_bank=_lora_bank(model),
                           **kw)

    # reference arm: one engine, the same composed configuration
    single = make_engine()
    ids = [single.submit(Request(prompt=p, max_new_tokens=m, adapter=a,
                                 seed=i))
           for i, (p, m, a) in enumerate(reqs)]
    ref = {c.request_id: c for c in single.run_until_idle()}

    engines = [make_engine() for _ in range(2)]
    fr = FleetRouter(engines)
    calls = {"n": 0}
    real_get = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real_get(x)

    jax.device_get = counting
    try:
        gids = [fr.submit(Request(prompt=p, max_new_tokens=m, adapter=a,
                                  seed=i))
                for i, (p, m, a) in enumerate(reqs)]
        done = {c.request_id: c for c in fr.run_until_idle()}
    finally:
        jax.device_get = real_get
    assert [done[g].tokens for g in gids] == [ref[r].tokens for r in ids]
    assert fr.ledger.verify() == []
    assert calls["n"] == sum(
        e.n_chains + e.n_prefills + e.n_splices for e in engines
    )
    # affinity actually spread the stream: the shared-prefix family all
    # lands on one replica (that IS the point — splice hits), but the
    # whole fleet still saw work through it
    assert sum(e.n_prefills + e.n_splices for e in engines) == len(reqs)


def test_fleet_router_chaos_kill_redispatch_token_exact(model_params):
    """The ISSUE 12 acceptance pin on REAL engines: a chaos-killed
    replica's queued requests re-dispatch to survivors and finish
    byte-identical to a fault-free fleet run (same template + same seed
    => same greedy tokens — the re-dispatch is invisible in outputs);
    in-flight work on the dead replica completes ``"replica_dead"``;
    the ledger proves exactly-once; the killed engine's device work
    stops at the kill."""
    from pytorch_distributed_training_tutorials_tpu.serve import (
        FleetRouter,
        affinity_hash,
    )
    from pytorch_distributed_training_tutorials_tpu.utils.chaos import FleetChaosConfig

    model, params = model_params
    n_replicas = 2
    base = _prompt(7200, 6)
    # one prompt family -> one affine replica holding in-flight AND
    # queued work when it dies (n_slots=1 keeps the rest queued)
    reqs = [(base, 12), (base, 12), (base, 12)]
    target = affinity_hash(base, adapter=0, depth=16) % n_replicas

    def run(chaos):
        engines = [
            ServeEngine(model, params, n_slots=1, tokens_per_launch=4,
                        max_queue=8)
            for _ in range(n_replicas)
        ]
        fr = FleetRouter(engines, chaos=chaos)
        gids = [fr.submit(Request(prompt=p, max_new_tokens=m, seed=i))
                for i, (p, m) in enumerate(reqs)]
        done = {c.request_id: c for c in fr.run_until_idle()}
        return fr, engines, [done[g] for g in gids]

    fr_ok, _, out_ok = run(None)
    assert [c.finish_reason for c in out_ok] == ["length"] * len(reqs)

    fr_x, engines_x, out_x = run(
        FleetChaosConfig(kill_replica=target, kill_at_chain=1)
    )
    assert fr_x.ledger.verify() == []
    assert len(out_x) == len(reqs)  # exactly one completion per request
    assert fr_x.replica_states()[target] == "dead"
    reasons = [c.finish_reason for c in out_x]
    assert "replica_dead" in reasons  # the in-flight casualty
    assert reasons.count("length") == len(reqs) - reasons.count(
        "replica_dead"
    )
    # every survivor is byte-identical to its fault-free twin
    for ok, x in zip(out_ok, out_x):
        if x.finish_reason == "length":
            assert x.tokens == ok.tokens
    assert fr_x.ledger.n_redispatched >= 1  # queued work actually moved
    # the dead replica is never stepped again: its chain counter froze
    # at (or just past) the kill threshold
    assert engines_x[target].n_chains <= 2


@pytest.mark.slow
def test_serve_selftest_router_subprocess(tmp_path):
    """``--selftest --router`` — the ISSUE 12 arm: a 3-replica fleet of
    real engines serves the staggered stream byte-identical to the
    single engine, then replays it with a chaos-killed replica —
    exactly-once delivery, token-exact re-dispatch, dead-replica
    accounting, and the summed fetch budget all counted into the
    receipt."""
    from pytorch_distributed_training_tutorials_tpu.obs import load_receipt, validate_receipt

    json_path = str(tmp_path / "selftest_router.json")
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tutorials_tpu.serve", "--selftest",
         "--router", "--json", json_path],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=os.environ.copy(),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    receipt = json.loads(out.stdout.strip().splitlines()[-1])
    assert receipt["ok"] is True, receipt.get("problems")
    assert validate_receipt(receipt, kind="serve_selftest") == []
    assert receipt["router_fleet_exact"] is True
    assert receipt["router_n_replicas"] == 3
    assert receipt["router_replicas_dead"] == 1
    assert receipt["router_redispatched"] + receipt[
        "router_replica_dead_completions"
    ] >= 1
    assert receipt["router_requests"] >= 3
    assert receipt["router_host_fetches_chaos"] >= 1
    assert load_receipt(json_path)["ok"] is True


# ---------------------------------------------- paged KV cache (ISSUE 13)

def _paged_geometry(pool_pages=6, page_size=8):
    """Oversubscribed by construction at the module CFG: 2 slots x
    64-token windows = 128 claimable tokens over a 48-token pool."""
    return dict(paged=True, page_size=page_size, pool_pages=pool_pages)


def test_paged_token_exact_oversubscribed(model_params):
    """The ISSUE 13 acceptance pin: a mixed short+long stream through a
    paged engine whose pool is SMALLER than n_slots * window is
    token-identical to the whole-slot engine and to one-shot
    ``generate()`` — pages, tables, and queued-for-pages waits are
    invisible in the outputs — and every page returns to the free list
    when the stream drains."""
    model, params = model_params
    reqs = [(_prompt(900 + i, p), m) for i, (p, m) in enumerate(
        [(3, 9), (17, 12), (5, 5), (12, 6), (2, 17), (9, 14)]
    )]
    eng_ws, out_ws = _run_stream(model, params, reqs)
    eng_pg, out_pg = _run_stream(model, params, reqs, **_paged_geometry())
    assert [c.tokens for c in out_pg] == [c.tokens for c in out_ws]
    for (p, m), c in zip(reqs, out_pg):
        assert c.tokens == _reference(model, params, p, m)
        assert c.finish_reason == "length"
    st = eng_pg.page_stats()
    assert st["paged"] == 1 and st["pages_in_use"] == 0
    assert 1 <= st["pages_high_water"] <= 6
    assert st["pages_allocs"] == st["pages_frees"]


def test_paged_admission_shed_and_validation(model_params):
    """A request that could never fit the pool sheds synchronously at
    submit (PoolExhausted, the QueueFull discipline — never a mid-decode
    failure); geometry errors are synchronous ValueErrors."""
    from pytorch_distributed_training_tutorials_tpu.serve import PoolExhausted

    model, params = model_params
    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, **_paged_geometry()
    )
    # 30 + 30 = 60 tokens -> 8 pages > the 6-page pool; note the
    # 64-token WINDOW would admit it — the pool is the binding check
    with pytest.raises(PoolExhausted):
        engine.submit(Request(prompt=_prompt(1, 30), max_new_tokens=30))
    assert engine.page_stats()["pages_sheds"] == 1
    # 24 + 24 = 48 tokens = exactly the pool: admitted
    rid = engine.submit(Request(prompt=_prompt(2, 24), max_new_tokens=24))
    out = {c.request_id: c for c in engine.run_until_idle()}
    assert out[rid].finish_reason == "length"
    with pytest.raises(ValueError):  # geometry without paged=True
        ServeEngine(model, params, n_slots=2, page_size=8)
    with pytest.raises(ValueError):  # paged without geometry
        ServeEngine(model, params, n_slots=2, paged=True)
    with pytest.raises(ValueError):  # window 64 not divisible
        ServeEngine(model, params, n_slots=2, paged=True, page_size=24,
                    pool_pages=4)


def test_paged_fetch_budget(model_params):
    """Paged engines keep the budget EXACTLY chains + prefills +
    splices: page-table updates ride the existing launches, the pool is
    host bookkeeping, and a prefix splice still costs its one scalar
    fetch."""
    model, params = model_params
    reqs = _overlap_stream(0.7, n_requests=6)
    calls = {"n": 0}
    real_get = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real_get(x)

    jax.device_get = counting
    try:
        engine, out = _run_stream(
            model, params, reqs, prefix_cache_bytes=16 * 1024 * 1024,
            **_paged_geometry(pool_pages=16),
        )
    finally:
        jax.device_get = real_get
    assert len(out) == len(reqs)
    assert calls["n"] == (
        engine.n_chains + engine.n_prefills + engine.n_splices
    )
    assert engine.n_splices >= 1  # the prefix path actually exercised


def test_paged_off_engine_unchanged(model_params):
    """paged=False (the default) keeps the pre-paged engine bit for
    bit: no pool/page-table leaves in the slot state, the decode model
    IS the caller's model (so every chain jaxpr is unchanged), none of
    the paged jit twins are even constructed, and page_stats() reports
    the subsystem off."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=8)
    explicit = ServeEngine(model, params, n_slots=2, tokens_per_launch=8,
                           paged=False)
    assert engine.page_stats() == {"paged": 0}
    assert engine._dec_model is model and explicit._dec_model is model
    for eng in (engine, explicit):
        leaf_names = {
            str(getattr(p[-1], "key", p[-1]))
            for p, _ in jax.tree_util.tree_flatten_with_path(
                eng._state["cache"]
            )[0]
        }
        assert "page_table" not in leaf_names
        assert not any(n.startswith("paged_") for n in leaf_names)
        assert not hasattr(eng, "_prefill_paged")
        assert not hasattr(eng, "_splice_paged")
    assert _tree_identical(engine._state, explicit._state)


def test_paged_prefix_shares_and_cow(model_params):
    """Prefix hits on a paged engine RETAIN shared pages instead of
    copying segments (pages_shares > 0), a hit whose depth straddles a
    page boundary triggers exactly the copy-on-write path (stamped as
    ``page_cow`` flight events), and the tokens stay byte-identical to
    the paged cache-off engine."""
    from pytorch_distributed_training_tutorials_tpu.obs.flight import FlightRecorder

    model, params = model_params
    # lengths 10/14 at 0.7 overlap give hit depths 7 and 9 — neither a
    # multiple of page_size 8, so the boundary-page CoW must fire
    reqs = _overlap_stream(0.7, n_requests=8)
    eng_off, out_off = _run_stream(model, params, reqs,
                                   **_paged_geometry(pool_pages=16))
    rec = FlightRecorder(capacity=512)
    eng_on, out_on = _run_stream(
        model, params, reqs, prefix_cache_bytes=16 * 1024 * 1024,
        flight=rec, **_paged_geometry(pool_pages=16),
    )
    assert [c.tokens for c in out_on] == [c.tokens for c in out_off]
    assert eng_on.n_splices >= 1
    st = eng_on.page_stats()
    assert st["pages_shares"] >= 1
    assert rec.kind_counts["page_cow"] >= 1
    # retained segments hold pages after the drain; evicting them
    # through the index returns every page to the pool (the on_evict
    # hook wiring)
    while eng_on.prefix.evict_coldest():
        pass
    assert eng_on.page_stats()["pages_in_use"] == 0


def test_paged_pool_shed_flight_event(model_params):
    """An admission-time shed is stamped as a host-only ``pool_shed``
    flight event naming the request geometry — page pressure is visible
    in the flight log without any device work."""
    from pytorch_distributed_training_tutorials_tpu.obs.flight import FlightRecorder
    from pytorch_distributed_training_tutorials_tpu.serve import PoolExhausted

    model, params = model_params
    rec = FlightRecorder(capacity=64)
    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, flight=rec,
        **_paged_geometry(),
    )
    with pytest.raises(PoolExhausted):
        engine.submit(Request(prompt=_prompt(3, 30), max_new_tokens=30))
    assert rec.kind_counts["pool_shed"] == 1
    ev = [e for e in rec.events if e["kind"] == "pool_shed"]
    assert ev and ev[0]["pages"] == 8 and ev[0]["p_len"] == 30


@pytest.mark.parametrize(
    "cfg_kwargs",
    [
        pytest.param(dict(scan_layers=True), marks=pytest.mark.slow),
        pytest.param(dict(n_kv_heads=2), marks=pytest.mark.slow),
        pytest.param(dict(kv_cache_dtype="int8"), marks=pytest.mark.slow),
    ],
    ids=["scan_layers", "gqa", "int8kv"],
)
def test_paged_token_exact_layouts(cfg_kwargs):
    """The page-granular slot surgery generalizes across the scanned
    (leading layer axis), GQA, and int8-KV cache layouts: paged output
    stays engine-vs-engine token-exact on the oversubscribed stream."""
    import dataclasses

    cfg = dataclasses.replace(CFG, **cfg_kwargs)
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    reqs = [(_prompt(950 + i, p), m) for i, (p, m) in enumerate(
        [(3, 9), (17, 12), (12, 6), (2, 17)]
    )]
    _, out_ws = _run_stream(model, params, reqs)
    _, out_pg = _run_stream(model, params, reqs, **_paged_geometry())
    assert [c.tokens for c in out_pg] == [c.tokens for c in out_ws]


@pytest.mark.slow
def test_paged_composed_spec_adapters_pipeline(model_params):
    """The full composition: paged + prefix cache + speculation +
    multi-tenant adapters + depth-2 pipelining with chunked prefill is
    token-exact to the same composition on the whole-slot engine —
    every subsystem reads the cache through the same paged path."""
    model, params = model_params
    bank = _lora_bank(model)
    reqs = _overlap_stream(0.7, n_requests=8)
    kw = dict(
        prefix_cache_bytes=16 * 1024 * 1024, speculative_k=2,
        adapter_bank=bank, pipeline_depth=2, prefill_chunk=8,
    )

    def run(**extra):
        engine = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8, **kw, **extra
        )
        ids = [
            engine.submit(Request(prompt=p, max_new_tokens=m, seed=i,
                                  adapter=(i % 3) % 2 + 1 if i % 3 else 0))
            for i, (p, m) in enumerate(reqs)
        ]
        out = {c.request_id: c for c in engine.run_until_idle()}
        return [out[r].tokens for r in ids]

    assert run(**_paged_geometry(pool_pages=16)) == run()


@pytest.mark.slow
def test_serve_selftest_paged_subprocess(tmp_path):
    """``--selftest --paged`` — the ISSUE 13 arm: an oversubscribed
    mixed stream through a page-pool engine is token-identical to
    whole-slot with the fetch budget intact, a pool-exceeding request
    sheds at submit, and the prefix leg shows copy-free page sharing,
    all counted into the receipt."""
    from pytorch_distributed_training_tutorials_tpu.obs import load_receipt, validate_receipt

    json_path = str(tmp_path / "selftest_paged.json")
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tutorials_tpu.serve", "--selftest",
         "--paged", "--json", json_path],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=os.environ.copy(),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    receipt = json.loads(out.stdout.strip().splitlines()[-1])
    assert receipt["ok"] is True, receipt.get("problems")
    assert validate_receipt(receipt, kind="serve_selftest") == []
    assert receipt["paged_token_exact"] is True
    assert receipt["paged_prefix_token_exact"] is True
    assert receipt["paged_shed_ok"] is True
    assert receipt["paged"] == 1 and receipt["pool_pages"] == 6
    assert receipt["pages_sheds"] == 1
    assert receipt["paged_prefix_shares"] >= 1
    assert receipt["pages_in_use"] == 0
    assert receipt["hbm_high_water_bytes"] > 0
    # the ISSUE 17 legs: fused kernel read path + packed int4 KV
    assert receipt["paged_kernel_token_exact"] is True
    assert receipt["paged_int4_page_bytes_halved"] is True
    assert receipt["paged_int4_ok"] is True
    assert receipt["paged_int4_pool_pages"] == 12
    assert load_receipt(json_path)["ok"] is True


# ------------------------------------------- fused paged kernel + int4 KV
# (ISSUE 17): the Pallas page-walk read path and the packed-nibble KV
# family. Contracts: kernel-on full-precision greedy is token-exact to
# the gather engine (the reference oracle) across layouts and the full
# subsystem composition; the compiled kernel chain never materializes a
# dense (slots, window, ...) gathered KV window (the fused_loss-style
# no-live-buffer receipt); kernel-off / kv_bits-off engines are
# byte-identical; int4 page_bytes is EXACTLY half of int8's.


def test_paged_kernel_token_exact_base(model_params):
    """The core ISSUE 17 pin: the fused page-walk kernel is invisible in
    full-precision greedy tokens on the oversubscribed mixed stream —
    and therefore (by the ISSUE 13 pin) exact vs whole-slot and
    generate() too. Budget unchanged: chains + prefills."""
    model, params = model_params
    reqs = [(_prompt(970 + i, p), m) for i, (p, m) in enumerate(
        [(3, 9), (17, 12), (5, 5), (2, 17)]
    )]
    _, out_g = _run_stream(model, params, reqs, **_paged_geometry())
    calls = {"n": 0}
    real_get = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real_get(x)

    jax.device_get = counting
    try:
        eng_k, out_k = _run_stream(
            model, params, reqs, paged_kernel=True, **_paged_geometry()
        )
    finally:
        jax.device_get = real_get
    assert [c.tokens for c in out_k] == [c.tokens for c in out_g]
    assert calls["n"] == eng_k.n_chains + eng_k.n_prefills
    st = eng_k.page_stats()
    assert st["paged_kernel"] == 1 and st["kv_bits"] == 0


@pytest.mark.parametrize(
    "cfg_kwargs",
    [
        pytest.param(dict(scan_layers=True), marks=pytest.mark.slow),
        pytest.param(dict(n_kv_heads=2), marks=pytest.mark.slow),
        pytest.param(dict(kv_cache_dtype="int8"), marks=pytest.mark.slow),
        pytest.param(dict(kv_cache_dtype="int4"), marks=pytest.mark.slow),
    ],
    ids=["scan_layers", "gqa", "int8kv", "int4kv"],
)
def test_paged_kernel_token_exact_layouts(cfg_kwargs):
    """Kernel-vs-gather engine exactness generalizes across the scanned,
    GQA, int8-KV, and int4-KV cache layouts: both read paths see the
    same stored (possibly quantized) K/V, so tokens match even where
    quantization itself moved them off full precision."""
    import dataclasses

    cfg = dataclasses.replace(CFG, **cfg_kwargs)
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    reqs = [(_prompt(975 + i, p), m) for i, (p, m) in enumerate(
        [(3, 9), (17, 12), (2, 17)]
    )]
    _, out_g = _run_stream(model, params, reqs, **_paged_geometry())
    _, out_k = _run_stream(model, params, reqs, paged_kernel=True,
                           **_paged_geometry())
    assert [c.tokens for c in out_k] == [c.tokens for c in out_g]


@pytest.mark.slow
def test_paged_kernel_composed_spec_adapters_pipeline(model_params):
    """The full composition through the kernel read path: paged + prefix
    cache + speculation + multi-tenant adapters + depth-2 pipelining
    with chunked prefill, token-exact to the same composition on the
    gather engine (splice seeds, verify forwards, and chunk
    continuations all route their S>1 reads through the kernel)."""
    model, params = model_params
    bank = _lora_bank(model)
    reqs = _overlap_stream(0.7, n_requests=8)
    kw = dict(
        prefix_cache_bytes=16 * 1024 * 1024, speculative_k=2,
        adapter_bank=bank, pipeline_depth=2, prefill_chunk=8,
        **_paged_geometry(pool_pages=16),
    )

    def run(**extra):
        engine = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8, **kw, **extra
        )
        ids = [
            engine.submit(Request(prompt=p, max_new_tokens=m, seed=i,
                                  adapter=(i % 3) % 2 + 1 if i % 3 else 0))
            for i, (p, m) in enumerate(reqs)
        ]
        out = {c.request_id: c for c in engine.run_until_idle()}
        return [out[r].tokens for r in ids]

    assert run(paged_kernel=True) == run()


def _chain_hlo(engine) -> str:
    """AOT-compiled decode-chain HLO (the audit_decode_hlo idiom: one
    extra compile, fine on the CPU mesh)."""
    return engine._chain.lower(
        engine.params, engine._state
    ).compile().as_text()


def _window_shapes(ns, w, kv):
    """Shape-literal regexes for a dense gathered KV window: every
    storage dtype the cache families use, any head_dim — the
    fused_loss-style no-live-buffer patterns."""
    return [
        rf"(f32|bf16|f16|s8|u8)\[{ns},{w},{kv},\d+\]",
        # scanned layouts put the layer axis first
        rf"(f32|bf16|f16|s8|u8)\[\d+,{ns},{w},{kv},\d+\]",
    ]


def test_paged_kernel_no_dense_window_in_chain_hlo(model_params):
    """The acceptance receipt: the compiled kernel-path decode chain
    contains NO dense (n_slots, window, kv, d) gathered temporary —
    while the gather path (the positive control proving the patterns
    detect what they claim) provably does."""
    import re

    model, params = model_params
    ns, w, kv = 2, CFG.max_seq_len, CFG.n_heads
    mk = lambda **extra: ServeEngine(  # noqa: E731
        model, params, n_slots=ns, tokens_per_launch=8,
        **_paged_geometry(), **extra,
    )
    gather_txt = _chain_hlo(mk())
    kernel_txt = _chain_hlo(mk(paged_kernel=True))
    hit = [p for p in _window_shapes(ns, w, kv)
           if re.search(p, gather_txt)]
    assert hit, "positive control: gather chain must materialize the window"
    for pat in _window_shapes(ns, w, kv):
        assert not re.search(pat, kernel_txt), (
            f"kernel chain materializes a dense window: {pat}"
        )


def test_paged_kernel_off_engine_unchanged(model_params):
    """paged_kernel=False (the default) keeps the gather engine bit for
    bit: byte-identical slot state, the decode model's config carries
    the flag off (so every chain jaxpr is unchanged — the flag is
    trace-time structure), and page_stats reports it 0. kv_bits=None
    likewise changes nothing."""
    model, params = model_params
    eng = ServeEngine(model, params, n_slots=2, tokens_per_launch=8,
                      **_paged_geometry())
    explicit = ServeEngine(model, params, n_slots=2, tokens_per_launch=8,
                           paged_kernel=False, kv_bits=None,
                           **_paged_geometry())
    assert eng._dec_model.cfg.paged_kernel is False
    assert eng._dec_model.cfg == explicit._dec_model.cfg
    assert _tree_identical(eng._state, explicit._state)
    assert eng.page_stats()["paged_kernel"] == 0
    # the unpaged engine never even carries the flag's model twin
    plain = ServeEngine(model, params, n_slots=2, tokens_per_launch=8)
    assert plain._dec_model is model


def test_kv_bits_validation(model_params):
    """Engine-static knobs validate synchronously at construction."""
    model, params = model_params
    with pytest.raises(ValueError):  # kernel needs a page pool
        ServeEngine(model, params, n_slots=2, paged_kernel=True)
    with pytest.raises(ValueError):  # only None/8/4 exist
        ServeEngine(model, params, n_slots=2, kv_bits=2)


def test_kv_bits_int4_doubles_pages_at_equal_hbm(model_params):
    """The 2x claim as an identity, not an approximation: int4 storage
    (packed nibbles + bf16 scales) prices page_bytes at EXACTLY half of
    int8's (d/2 + 2 vs d + 4 bytes per token-head), so a 2x-page pool
    costs the same HBM — and the oversubscribed stream still completes
    through the kernel read path within the unchanged fetch budget."""
    model, params = model_params
    reqs = [(_prompt(985 + i, p), m) for i, (p, m) in enumerate(
        [(3, 9), (17, 12), (2, 17)]
    )]
    eng8, out8 = _run_stream(model, params, reqs, kv_bits=8,
                             **_paged_geometry(pool_pages=6))
    eng4, out4 = _run_stream(model, params, reqs, kv_bits=4,
                             paged_kernel=True,
                             **_paged_geometry(pool_pages=12))
    s8, s4 = eng8.page_stats(), eng4.page_stats()
    assert s4["page_bytes"] * 2 == s8["page_bytes"]
    assert (s4["pool_pages"] * s4["page_bytes"]
            == s8["pool_pages"] * s8["page_bytes"])
    assert s8["kv_bits"] == 8 and s4["kv_bits"] == 4
    for (_, m), c in zip(reqs, out4):
        assert len(c.tokens) == m and c.finish_reason == "length"
    # int4 leaf families: packed uint8 K/V at half head_dim, bf16 scales
    leaves = {
        str(getattr(p[-1], "key", p[-1])): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(
            eng4._state["cache"]
        )[0]
    }
    assert leaves["paged_key"].dtype == jnp.uint8
    assert leaves["paged_key_scale"].dtype == jnp.bfloat16
    k8 = {
        str(getattr(p[-1], "key", p[-1])): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(
            eng8._state["cache"]
        )[0]
    }
    assert leaves["paged_key"].shape[-1] * 2 == k8["paged_key"].shape[-1]


def test_kv_bits_follows_model_config(model_params):
    """kv_bits=4 on a full-precision model is the same engine as
    kv_bits=None on a model whose config already says "int4" — the
    kwarg is a config override, not a second quantization path."""
    import dataclasses

    model, params = model_params
    reqs = [(_prompt(995 + i, p), m)
            for i, (p, m) in enumerate([(5, 8), (9, 6)])]
    _, out_kw = _run_stream(model, params, reqs, kv_bits=4)
    cfg4 = dataclasses.replace(CFG, kv_cache_dtype="int4")
    model4 = TransformerLM(cfg4)
    _, out_cfg = _run_stream(model4, params, reqs)
    assert [c.tokens for c in out_kw] == [c.tokens for c in out_cfg]


@pytest.mark.slow
def test_serve_selftest_tp_subprocess(tmp_path):
    """``--selftest --tp 2`` — the ISSUE 15 arm: the base staggered
    stream replayed through a head-sharded engine is token-identical
    with the fetch budget intact (one batched fetch per chain), the
    compiled decode chain audits all-reduce-only, and per-chip KV
    bytes land at half the global cache — all counted into the
    receipt."""
    from pytorch_distributed_training_tutorials_tpu.obs import load_receipt, validate_receipt

    json_path = str(tmp_path / "selftest_tp.json")
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tutorials_tpu.serve", "--selftest",
         "--tp", "2", "--json", json_path],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=os.environ.copy(),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    receipt = json.loads(out.stdout.strip().splitlines()[-1])
    assert receipt["ok"] is True, receipt.get("problems")
    assert validate_receipt(receipt, kind="serve_selftest") == []
    assert receipt["tp"] == 2 and receipt["mesh_shape"] == "model:2"
    assert receipt["tp_token_exact"] is True
    assert receipt["tp_hlo_ok"] is True and receipt["tp_collectives"] > 0
    assert receipt["tp_kv_bytes_per_chip"] < receipt["tp_kv_bytes_global"]
    assert receipt["tp_host_fetches"] > 0
    assert load_receipt(json_path)["ok"] is True


# ------------------------------------------------ disaggregation (ISSUE 18)

def _disagg_fleet_run(model, params, reqs, pre_kw=None, dec_kw=None,
                      **shared):
    """Drive ``reqs`` = [(prompt, max_new, adapter), ...] through a
    1 prefill + 1 decode role fleet; returns (pre, dec, router,
    completions-in-submit-order)."""
    from pytorch_distributed_training_tutorials_tpu.serve import FleetRouter

    base = dict(n_slots=2, tokens_per_launch=8)
    base.update(shared)
    pre = ServeEngine(model, params, role="prefill",
                      **{**base, **(pre_kw or {})})
    dec = ServeEngine(model, params, role="decode",
                      **{**base, **(dec_kw or {})})
    fr = FleetRouter([pre, dec])
    gids = [fr.submit(Request(prompt=p, max_new_tokens=m, adapter=a,
                              seed=i))
            for i, (p, m, a) in enumerate(reqs)]
    done = {c.request_id: c for c in fr.run_until_idle()}
    return pre, dec, fr, [done[g] for g in gids]


def test_disagg_token_exact_mixed_lengths(model_params):
    """The ISSUE 18 acceptance pin: a 1p+1d role fleet serves staggered
    mixed-length greedy requests token-exact to one-shot generate() —
    the device-side KV handoff (extract on the prefill replica, splice
    surgery on the decode replica) is invisible in the tokens."""
    model, params = model_params
    reqs = [(_prompt(8000 + i, p), m, 0)
            for i, (p, m) in enumerate([(3, 9), (7, 12), (12, 6), (5, 17)])]
    pre, dec, fr, out = _disagg_fleet_run(model, params, reqs)
    for (p, m, _), c in zip(reqs, out):
        assert c.tokens == _reference(model, params, p, m)
        assert c.finish_reason == "length"
    # the split actually happened: every prefill ran on the prefill
    # replica, every chain on the decode replica
    assert pre.n_prefills == len(reqs) and pre.n_chains == 0
    assert dec.n_prefills == 0 and dec.n_chains > 0
    assert pre.n_handoffs_out == len(reqs)
    assert dec.n_handoffs_in == len(reqs)
    assert fr.ledger.verify() == []
    st = fr.router_stats()
    assert st["n_prefill_replicas"] == 1 and st["n_decode_replicas"] == 1
    assert st["handoffs_moved"] == len(reqs)


def test_disagg_fetch_budget(model_params, monkeypatch):
    """The fleet fetch budget under disaggregation: the prefill role
    fetches NOTHING (its handoff carries device futures), the decode
    role fetches once per chain plus once per ACCEPTED handoff — so the
    whole fleet's device_get count is exactly dec.n_chains +
    dec.n_handoffs_in, with the prefill replica contributing zero."""
    model, params = model_params
    reqs = [(_prompt(8100 + i, 4 + 3 * i), 10, 0) for i in range(3)]
    calls = {"n": 0}
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get",
        lambda x: (calls.__setitem__("n", calls["n"] + 1), real_get(x))[1],
    )
    pre, dec, fr, out = _disagg_fleet_run(model, params, reqs)
    assert len(out) == 3 and all(c.finish_reason == "length" for c in out)
    assert dec.n_handoffs_in == 3
    # every fetch in the run is accounted to the decode role: chains +
    # handoffs. Nothing left for the prefill role to have spent.
    assert calls["n"] == dec.n_chains + dec.n_handoffs_in
    assert pre.n_prefills == 3 and pre.n_splices == 0


def test_disagg_role_validation(model_params):
    """Role construction rejects the other side's machinery, and the
    role-specific entry points reject the wrong role — admission
    failures are synchronous, never a mid-decode surprise."""
    model, params = model_params
    with pytest.raises(ValueError):
        ServeEngine(model, params, role="tokenize")
    for bad_kw in (dict(speculative_k=2), dict(pipeline_depth=2),
                   _paged_geometry()):
        with pytest.raises(ValueError):
            ServeEngine(model, params, role="prefill", **bad_kw)
    for bad_kw in (dict(prefix_cache_bytes=1 << 20),
                   dict(prefill_chunk=8)):
        with pytest.raises(ValueError):
            ServeEngine(model, params, role="decode", **bad_kw)
    pre = ServeEngine(model, params, role="prefill", n_slots=1)
    dec = ServeEngine(model, params, role="decode", n_slots=1)
    with pytest.raises(ValueError):
        dec.submit(Request(prompt=[1, 2], max_new_tokens=2))
    with pytest.raises(ValueError):
        pre.accept(Request(prompt=[1, 2], max_new_tokens=2), None)
    with pytest.raises(ValueError):
        dec.take_handoff(0)


def test_disagg_role_none_off_path(model_params):
    """role=None is the monolithic engine: NO handoff programs are
    constructed (compiled-program census unchanged), the handoff
    counters stay zero through a served stream, and role_stats reports
    the off marker."""
    model, params = model_params
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=8)
    assert engine.role is None
    assert not hasattr(engine, "_handoff_prefill")
    assert not hasattr(engine, "_accept_jit")
    engine.submit(Request(prompt=_prompt(8200, 5), max_new_tokens=6))
    engine.run_until_idle()
    assert engine.n_handoffs_out == 0 and engine.n_handoffs_in == 0
    assert engine.role_stats() == {"role": 0}
    assert engine.stats("role") == {"role": 0}
    with pytest.raises(ValueError):
        engine.take_handoff(0)


def test_disagg_direct_handoff_token_exact(model_params):
    """The engine-level contract without a router: submit to the
    prefill engine, move its Handoff into the decode engine by hand,
    and the decoded stream still matches generate() — the handoff API
    is complete on its own (heterogeneous fleets can drive it)."""
    import dataclasses as _dc

    model, params = model_params
    pre = ServeEngine(model, params, role="prefill", n_slots=2,
                      tokens_per_launch=8)
    dec = ServeEngine(model, params, role="decode", n_slots=2,
                      tokens_per_launch=8)
    reqs = [(_prompt(8300 + i, p), m) for i, (p, m) in
            enumerate([(4, 8), (9, 11)])]
    for i, (p, m) in enumerate(reqs):
        tmpl = Request(prompt=p, max_new_tokens=m, seed=i)
        rid = pre.submit(_dc.replace(tmpl))
        comps = pre.run_until_idle()
        assert [c.finish_reason for c in comps] == ["handoff"]
        assert comps[0].tokens == []
        dec.accept(tmpl, pre.take_handoff(rid))
    done = dec.run_until_idle()
    assert sorted(len(c.tokens) for c in done) == sorted(
        m for _, m in reqs
    )
    by_len = {len(c.tokens): c for c in done}
    for p, m in reqs:
        assert by_len[m].tokens == _reference(model, params, p, m)


@pytest.mark.parametrize(
    "cfg_kwargs",
    [
        pytest.param(dict(scan_layers=True), marks=pytest.mark.slow),
        pytest.param(dict(n_kv_heads=2), marks=pytest.mark.slow),
        pytest.param(dict(kv_cache_dtype="int8"), marks=pytest.mark.slow),
    ],
    ids=["scan_layers", "gqa", "int8_kv"],
)
def test_disagg_token_exact_layouts(cfg_kwargs):
    """The handoff surgery on the variant cache layouts (scan-stacked,
    GQA-shrunk, int8-quantized leaves + scales): disaggregated greedy
    matches the MONOLITHIC engine token for token (int8's rounded
    near-ties make engine-vs-engine the right oracle; the unrolled
    full-precision arm pins generate()-exactness above)."""
    import dataclasses as _dc

    cfg = _dc.replace(CFG, **cfg_kwargs)
    model, params = _make(cfg)
    reqs = [(_prompt(8400 + i, p), m, 0)
            for i, (p, m) in enumerate([(4, 9), (9, 7), (13, 11)])]
    mono = ServeEngine(model, params, n_slots=2, tokens_per_launch=8)
    ids = [mono.submit(Request(prompt=p, max_new_tokens=m, seed=i))
           for i, (p, m, _) in enumerate(reqs)]
    ref = {c.request_id: c for c in mono.run_until_idle()}
    _, _, fr, out = _disagg_fleet_run(model, params, reqs)
    assert [c.tokens for c in out] == [ref[i].tokens for i in ids]
    assert fr.ledger.verify() == []


@pytest.mark.slow
def test_disagg_composed_full_stack(model_params):
    """The everything-composed acceptance arm: prefill replica with
    prefix cache + chunked prefill, decode replica with speculation +
    paged KV + depth-2 pipelining, adapter banks on BOTH (the factors
    act in prefill and decode forwards alike) — a mixed-tenant
    shared-prefix stream is token-exact to one monolithic engine
    running the same full stack, with the ledger proving exactly-once
    across every handoff."""
    model, params = model_params
    shared = _prompt(8500, 12)
    reqs = [(shared + _prompt(8501 + i, 5), 5 + (i % 3), i % 3)
            for i in range(6)]
    mono = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8,
        prefix_cache_bytes=16 * 1024 * 1024, prefill_chunk=8,
        speculative_k=2, pipeline_depth=2,
        adapter_bank=_lora_bank(model), **_paged_geometry(),
    )
    ids = [mono.submit(Request(prompt=p, max_new_tokens=m, adapter=a,
                               seed=i))
           for i, (p, m, a) in enumerate(reqs)]
    ref = {c.request_id: c for c in mono.run_until_idle()}
    pre, dec, fr, out = _disagg_fleet_run(
        model, params, reqs,
        pre_kw=dict(prefix_cache_bytes=16 * 1024 * 1024, prefill_chunk=8,
                    adapter_bank=_lora_bank(model)),
        dec_kw=dict(speculative_k=2, pipeline_depth=2,
                    adapter_bank=_lora_bank(model), **_paged_geometry()),
    )
    assert [c.tokens for c in out] == [ref[i].tokens for i in ids]
    # the composed machinery actually engaged on each side
    assert pre.n_splices > 0          # shared prefix spliced on prefill
    assert dec.page_stats()["paged"] == 1
    assert fr.ledger.verify() == []
    assert fr.router_stats()["handoffs_moved"] == len(reqs)


# --------------------------------------------------- SLO tiers (ISSUE 20)
# priority scheduling + preemption by KV swap. tests/test_slo.py holds the
# thorough pins (swap roundtrip across layouts, paged pool pressure, the
# composed arm, the chaos injector); the tests here are the two
# engine-contract halves CLAUDE.md requires to live NEXT TO the other
# budget spies: the GROWN fetch budget (chains + prefills + splices +
# counted swap-outs) and the priority-off byte-identity marker.


def test_slo_fetch_budget_with_swaps(model_params, monkeypatch):
    """The ISSUE 20 budget rule: a preemption's swap-OUT spends exactly
    ONE counted batched fetch (the parked segment tree leaves in one
    ``device_get``) and the swap-in re-splice spends ZERO — total calls
    == chains + prefills + splices + n_swaps_out. Same counting-spy
    idiom as the prefix/robustness budget pins; prompts precomputed
    OUTSIDE the spy window (_prompt itself fetches)."""
    model, params = model_params
    lo_prompt, hi_prompt = _prompt(9000, 3), _prompt(9001, 9)
    lo_ref = _reference(model, params, lo_prompt, 17)
    hi_ref = _reference(model, params, hi_prompt, 6)
    calls = {"n": 0}
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get",
        lambda x: (calls.__setitem__("n", calls["n"] + 1), real_get(x))[1],
    )
    engine = ServeEngine(
        model, params, n_slots=1, tokens_per_launch=8, priority_classes=2,
    )
    lo = Request(prompt=lo_prompt, max_new_tokens=17, priority=1)
    engine.submit(lo)
    done = {c.request_id: c for c in engine.step()}  # prefill + chain 1
    hi = Request(prompt=hi_prompt, max_new_tokens=6, priority=0)
    engine.submit(hi)
    while not engine.idle:
        for c in engine.step():
            done[c.request_id] = c
    assert engine.n_swaps_out >= 1 and engine.n_swaps_in >= 1
    assert calls["n"] == (engine.n_chains + engine.n_prefills
                          + engine.n_splices + engine.n_swaps_out)
    # and the preemption is invisible in the greedy tokens
    assert done[lo.request_id].tokens == lo_ref
    assert done[hi.request_id].tokens == hi_ref


def test_slo_single_class_equals_fifo_engine(model_params):
    """A priority engine fed ONLY one class never preempts and serves
    the stream token-identically to the default FIFO engine with the
    same compiled-program census — the scheduler swap is invisible
    until classes actually contend (test_slo.py holds the thorough
    off-path attr/state pins)."""
    from pytorch_distributed_training_tutorials_tpu.serve import FifoScheduler
    from pytorch_distributed_training_tutorials_tpu.serve.slo import PriorityScheduler

    model, params = model_params
    reqs = [(4, 6), (9, 5), (6, 8), (3, 7)]

    def run(**kw):
        engine = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8, **kw
        )
        ids = [
            engine.submit(Request(
                prompt=_prompt(9100 + i, p), max_new_tokens=m, seed=i,
            ))
            for i, (p, m) in enumerate(reqs)
        ]
        done = {c.request_id: c for c in engine.run_until_idle()}
        return engine, [done[i].tokens for i in ids]

    base_eng, base = run()
    slo_eng, slo = run(priority_classes=2)   # every request priority=0
    assert type(base_eng.scheduler) is FifoScheduler
    assert type(slo_eng.scheduler) is PriorityScheduler
    assert slo == base
    assert slo_eng.n_swaps_out == 0 and slo_eng.slo_stats()["n_preemptions"] == 0
    assert base_eng.slo_stats() == {"priority_classes": 0}
    assert slo_eng._chain._cache_size() == base_eng._chain._cache_size()
    assert slo_eng._prefill._cache_size() == base_eng._prefill._cache_size()


@pytest.mark.slow
def test_serve_selftest_slo_subprocess(tmp_path):
    """``--selftest --slo`` — the ISSUE 20 arm: a 1-slot priority engine
    preempts its low-class slot for a class-0 arrival (KV swap to host,
    resume splice), both streams token-exact to generate(), the fetch
    budget = chains + prefills + splices + counted swaps balanced under
    the contract sentry, plus the chaos forced-preempt and the
    single-class FIFO-order legs."""
    from pytorch_distributed_training_tutorials_tpu.obs import load_receipt, validate_receipt

    json_path = str(tmp_path / "selftest_slo.json")
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tutorials_tpu.serve", "--selftest",
         "--slo", "--json", json_path],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=os.environ.copy(),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    receipt = json.loads(out.stdout.strip().splitlines()[-1])
    assert receipt["ok"] is True, receipt.get("problems")
    assert validate_receipt(receipt, kind="serve_selftest") == []
    assert receipt["slo_token_exact"] is True
    assert receipt["slo_chaos_token_exact"] is True
    assert receipt["slo_single_class_fifo_identical"] is True
    assert receipt["priority_classes"] == 2
    assert receipt["n_preemptions"] >= 1
    assert receipt["n_swaps_out"] >= 1 and receipt["n_swaps_in"] >= 1
    assert receipt["slo_host_fetches"] <= receipt["slo_fetch_budget"]
    assert load_receipt(json_path)["ok"] is True

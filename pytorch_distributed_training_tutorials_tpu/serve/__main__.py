"""``python -m pytorch_distributed_training_tutorials_tpu.serve --selftest``: end-to-end smoke of the
continuous-batching engine on a tiny model, any backend.

Exercises the whole serving loop the way tier-1 exercises ``obs``: a toy
:class:`..models.transformer.TransformerLM` serves a staggered stream of
mixed-length greedy requests through :class:`..serve.ServeEngine`, and
every completion is checked TOKEN-EXACT against one-shot
:func:`..models.generate.generate` of the same model/params — the
continuous-batching machinery (slot refill, bucketed prefill, per-slot
positions, chained decode) must be invisible in the outputs. Also pins
backpressure (:class:`..serve.QueueFull`) and the fetch discipline (at
most one ``jax.device_get`` per decode chain, counted by monkeypatching).
A second arm replays an overlapping-prompt stream (one shared prefix
family, per-request tails) through two engines — prefix cache OFF and ON
(``prefix_cache_bytes``) — and requires byte-identical greedy tokens, a
hit rate > 0, FEWER full prefills (splices replace them, counted not
estimated), and the same one-fetch-per-chain budget with splices
included. A third (``--spec-k``) arm replays a repetitive/templated
stream through a ``speculative_k > 0`` engine: greedy tokens must stay
byte-identical to the non-speculative engine, the fetch budget is
unchanged (the (S, T, k+1) block + counts ride the chain's ONE batched
fetch), and the MECHANISM must have fired — mean accepted length > 1
and sequential verify forwards strictly below tokens emitted (the whole
point of speculation: fewer sequential decode steps than tokens).
A fourth (``--adapters``) arm registers N-1 tenants with distinct LoRA
factors into an :class:`..adapters.AdapterBank` and replays a
mixed-tenant stream: every request's greedy tokens must be byte-identical
to a DEDICATED single-tenant engine over the same bank, id-0 requests
byte-identical to the bank-less base engine, the fetch budget unchanged,
and admission of an unregistered id must fail synchronously at submit.
A fifth (``--chaos``) arm runs the ISSUE 9 fault-injection gauntlet:
one guarded engine takes an injected NaN-logit (the poisoned request
must finish ``"nonfinite"`` with its clean prefix of tokens while the
co-scheduled request stays byte-identical to a fault-free run), a
deadline expiry, a host-side cancel and a close/drain — fetch budget
still counted — and a mini training leg drives the skip-step guard
(poisoned batch leaves TrainState bitwise unchanged, the skip counter
increments once). With a recorder riding along, the chaos injectors
auto-dump ``graft-flightlog/v1`` snapshots whose trigger names the
quarantined slot — the post-mortem contract tests assert on. A sixth
(``--flight``) arm replays the staggered stream through a
:class:`..obs.flight.FlightRecorder`-instrumented engine: tokens stay
byte-identical, the fetch budget is unchanged (the recorder is pure host
bookkeeping), every completed request carries a FULL lifecycle span
(submit -> queue_pop -> prefill -> complete), per-stage event counts
reconcile with the engine's own counters, and the streaming-histogram
p50/p95 match sort-based percentiles within one bucket's documented
relative error. The receipt gains the ``fault_stats()`` fields plus
``steps_skipped``, and the per-arm stats now flow through ONE
``engine.stats(part)`` aggregate. A seventh (``--pipeline``) arm replays
the staggered stream through a ``pipeline_depth=2`` + chunked-prefill
engine (ISSUE 11): greedy tokens must stay byte-identical to the serial
engine (double-buffering moves the fetch off the critical path, never
changes what was computed), the fetch budget is unchanged (mid-prefill
chunks are pure dispatch — no fetch until the final chunk), and the
chunking mechanism must have fired (``n_chunks > 0`` on a stream whose
longest prompt exceeds the chunk). An eighth (``--router``) arm runs a
3-replica fleet of real engines behind :class:`..serve.FleetRouter`
(ISSUE 12): a fault-free leg must be byte-identical to the single
engine (routing is invisible), then the same stream replays with one
replica chaos-killed mid-stream — the DispatchLedger must verify
exactly-once (no accepted request lost or completed twice),
re-dispatched requests must stay byte-identical to the fault-free leg,
and the SUMMED per-replica fetch budget stays chains + prefills +
splices. A ninth (``--paged``) arm replays a short+long mixed stream at
OVERSUBSCRIBED slot count (``n_slots * window > pool_pages *
page_size``) through a ``paged=True`` engine (ISSUE 13): greedy tokens
must stay byte-identical to the whole-slot engine (pages are invisible
in the outputs), the fetch budget is unchanged, a request that can
never fit the pool must shed synchronously at submit
(:class:`..serve.pages.PoolExhausted`), and an overlapping-prompt leg
with the prefix cache ON must show page SHARES (retained prefix pages
seeding new requests copy-free) while staying byte-identical to the
paged cache-off leg. ``page_stats()`` (occupancy high-water, shares,
sheds) rides into the receipt. The paged arm also runs the ISSUE 17
legs: the fused Pallas page-walk kernel (``paged_kernel=True``) must be
token-exact to the gather engine at full precision, and the int4
packed-KV engine (``kv_bits=4``) must price ``page_bytes`` at EXACTLY
half the int8 engine's — 2x the pages at equal pool HBM — while
completing the same stream through the kernel read path within the
unchanged fetch budget. A tenth (``--tp N``) arm replays the
base staggered stream through a :class:`..parallel.TensorParallel`-
sharded engine on a ``{'model': N}`` mesh (ISSUE 15): greedy tokens
must stay byte-identical to the replicated engine, the fetch budget is
unchanged (ONE batched fetch per chain regardless of mesh width), the
KV slot state must REALLY shard (per-chip bytes strictly below global),
and the compiled decode chain's HLO must pass the collective audit
(``audit_decode_hlo`` — nothing beyond the whitelisted all-reduces).
``tp_*`` receipt fields carry the audit verdict and per-chip KV bytes.
An eleventh (``--sentry``) arm runs the runtime contract sentry
(ISSUE 19) — the production twin of this harness's own monkeypatch
spies: a :class:`..obs.sentry.ContractSentry`-instrumented engine warms
up the base stream, ``mark_steady()``s, then replays it — the steady
leg must show ZERO steady recompiles, a fetch count equal to an
independent monkeypatch spy AND to the engine's declared budget, and
zero host-numpy re-uploads, with greedy tokens byte-identical to the
uninstrumented engine. Then one injected violation per probe class (a
post-steady jit of a fresh program over a prebuilt operand, a stray
``device_get`` inside one step round, a host-numpy arg tree) must each
yield exactly one typed flight event and one ``graft-flightlog/v1``
auto-dump whose trigger names the violation; the device-resident twin
of the numpy tree must stay silent. ``sentry_*`` receipt fields carry
the clean-leg summary plus the three caught-flags.
A twelfth (``--slo``) arm runs the SLO-tier gauntlet (ISSUE 20): a
``priority_classes=2`` engine decodes a low-class request on its only
slot when a class-0 request arrives — the engine must PREEMPT (KV
swap-out to host, counted ``n_swaps_out``), serve the interactive
request, then swap the victim back in; BOTH streams must be
token-exact to one-shot ``generate()`` (preemption is invisible in the
tokens), the fetch budget is chains + prefills + splices + counted
swaps (the monkeypatch spy counts the swap-out's one batched segment
fetch), and a :class:`..obs.sentry.ContractSentry` riding the same
stream must close every round balanced — the runtime proof that swap
fetches flow through the budgeted ``_sentry_fetch`` seam. A chaos leg
(``preempt_at_chain``) force-preempts a slot with NO real pressure: the
victim resumes token-exact and the co-scheduled slot's tokens are
byte-identical to a preemption-free run. A host-only leg pins
``PriorityScheduler(n_classes=1)`` pop-order-identical to
``FifoScheduler`` over the same submission sequence.
Prints exactly one JSON line (a ``graft-receipt/v1`` envelope) and
exits non-zero on any failure.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def selftest(json_path: str | None = None, spec_k: int = 2,
             adapters: int = 3, chaos: bool = False,
             flight: bool = False, pipeline: bool = False,
             router: bool = False, paged: bool = False,
             tp: int = 0, sentry: bool = False,
             slo: bool = False) -> dict:
    import math
    import tempfile

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tutorials_tpu.adapters import (
        AdapterBank,
        extract_adapter,
        lora_init,
    )
    from pytorch_distributed_training_tutorials_tpu.models.generate import generate
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.obs import make_receipt, validate_receipt
    from pytorch_distributed_training_tutorials_tpu.serve import QueueFull, Request, ServeEngine

    problems: list[str] = []
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, max_seq_len=64
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]

    engine = ServeEngine(
        model, params, n_slots=2, tokens_per_launch=8, max_queue=2
    )

    # a staggered stream with mixed prompt lengths and budgets: 2 slots,
    # 5 requests, the last submitted only after capacity frees up
    rng = jax.random.PRNGKey(1)
    prompts = []
    for i, (p_len, max_new) in enumerate(
        [(3, 9), (7, 12), (5, 1), (12, 6), (2, 17)]
    ):
        rng, sub = jax.random.split(rng)
        toks = jax.device_get(
            jax.random.randint(sub, (p_len,), 0, cfg.vocab_size)
        ).tolist()
        prompts.append((toks, max_new))

    fetches = {"n": 0}
    real_get = jax.device_get

    def counting_get(x):
        fetches["n"] += 1
        return real_get(x)

    jax.device_get = counting_get
    try:
        completions = {}
        backpressured = False
        pending = list(prompts)
        # submit two, then drip the rest in as steps run — staggered
        # arrivals against live slots
        for toks, max_new in pending[:2]:
            engine.submit(Request(prompt=toks, max_new_tokens=max_new))
        pending = pending[2:]
        while not engine.idle or pending:
            while pending:
                toks, max_new = pending[0]
                try:
                    engine.submit(
                        Request(prompt=toks, max_new_tokens=max_new)
                    )
                    pending.pop(0)
                except QueueFull:
                    backpressured = True
                    break
            for c in engine.step():
                completions[c.request_id] = c
        n_chains, n_fetch = engine.n_chains, fetches["n"]
    finally:
        jax.device_get = real_get
    if len(completions) != len(prompts):
        problems.append(
            f"{len(completions)} completions for {len(prompts)} requests"
        )
    # fetch discipline: one fetch per chain + one scalar per prefill
    budget = n_chains + engine.n_prefills
    if n_fetch > budget:
        problems.append(
            f"{n_fetch} host fetches > {budget} "
            f"({n_chains} chains + {engine.n_prefills} prefills)"
        )

    # token-exactness vs one-shot generate(), greedy, per request
    mismatches = 0
    for rid, (toks, max_new) in enumerate(prompts):
        ref = jax.device_get(
            generate(
                model, params, jnp.asarray([toks], jnp.int32), max_new
            )
        )[0, len(toks):].tolist()
        if completions[rid].tokens != ref:
            mismatches += 1
            problems.append(
                f"request {rid}: engine {completions[rid].tokens} != "
                f"generate {ref}"
            )
    # ------------------------------------------------------------------
    # prefix-cache arm: one shared prefix family, per-request tails;
    # cache ON must match cache OFF byte-for-byte while replacing full
    # prefills with splices (counted, not estimated)
    # ------------------------------------------------------------------
    rng, sub = jax.random.split(rng)
    shared = jax.device_get(
        jax.random.randint(sub, (16,), 0, cfg.vocab_size)
    ).tolist()
    overlap_reqs = []
    for i, (tail_len, max_new) in enumerate(
        [(3, 8), (5, 6), (2, 10), (4, 7), (3, 5), (6, 9)]
    ):
        rng, sub = jax.random.split(rng)
        tail = jax.device_get(
            jax.random.randint(sub, (tail_len,), 0, cfg.vocab_size)
        ).tolist()
        overlap_reqs.append((shared + tail, max_new))

    def run_stream(prefix_cache_bytes):
        eng = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8,
            prefix_cache_bytes=prefix_cache_bytes,
        )
        count = {"n": 0}

        def counting(x):
            count["n"] += 1
            return real_get(x)

        jax.device_get = counting
        try:
            out = {}
            pending = list(overlap_reqs)
            for toks, max_new in pending[:2]:
                eng.submit(Request(prompt=toks, max_new_tokens=max_new))
            pending = pending[2:]
            while not eng.idle or pending:
                while pending:
                    toks, max_new = pending[0]
                    try:
                        eng.submit(
                            Request(prompt=toks, max_new_tokens=max_new)
                        )
                        pending.pop(0)
                    except QueueFull:
                        break
                for c in eng.step():
                    out[c.request_id] = c.tokens
        finally:
            jax.device_get = real_get
        return eng, out, count["n"]

    eng_off, toks_off, _ = run_stream(0)
    eng_on, toks_on, fetches_on = run_stream(16 * 1024 * 1024)
    # the one stats() aggregate, part-filtered: each arm merges stats
    # from a DIFFERENT engine, and the filter keeps e.g. eng_spec's
    # "prefix_cache: 0" from clobbering eng_on's "prefix_cache: 1"
    stats = eng_on.stats("prefix")
    prefix_exact = toks_on == toks_off
    if not prefix_exact:
        problems.append(
            f"prefix cache changed greedy tokens: {toks_on} != {toks_off}"
        )
    if stats.get("prefix_hit_rate", 0) <= 0 or eng_on.n_splices < 1:
        problems.append(f"no prefix hits on an overlapping stream: {stats}")
    if eng_on.n_prefills >= eng_off.n_prefills:
        problems.append(
            f"prefix cache saved no prefills: {eng_on.n_prefills} on vs "
            f"{eng_off.n_prefills} off"
        )
    on_budget = eng_on.n_chains + eng_on.n_prefills + eng_on.n_splices
    if fetches_on > on_budget:
        problems.append(
            f"prefix arm: {fetches_on} host fetches > {on_budget} "
            f"({eng_on.n_chains} chains + {eng_on.n_prefills} prefills + "
            f"{eng_on.n_splices} splices)"
        )

    # ------------------------------------------------------------------
    # speculative arm: a repetitive/templated stream (the workload
    # prompt-lookup drafting exists for) through a speculate-k engine —
    # byte-identical greedy tokens vs the non-speculative engine, the
    # same fetch budget, AND the mechanism visibly firing: accepted
    # length > 1 and fewer sequential verify forwards than tokens out
    # ------------------------------------------------------------------
    template = [7, 8, 9, 10, 11]
    spec_reqs = []
    for i, (reps, max_new) in enumerate(
        [(4, 18), (3, 14), (4, 20), (3, 16)]
    ):
        spec_reqs.append((template * reps + [20 + i], max_new))

    def run_spec_stream(k):
        eng = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8,
            speculative_k=k,
        )
        count = {"n": 0}

        def counting(x):
            count["n"] += 1
            return real_get(x)

        jax.device_get = counting
        try:
            out = {}
            pending = list(spec_reqs)
            for toks, max_new in pending[:2]:
                eng.submit(Request(prompt=toks, max_new_tokens=max_new))
            pending = pending[2:]
            while not eng.idle or pending:
                while pending:
                    toks, max_new = pending[0]
                    try:
                        eng.submit(
                            Request(prompt=toks, max_new_tokens=max_new)
                        )
                        pending.pop(0)
                    except QueueFull:
                        break
                for c in eng.step():
                    out[c.request_id] = c.tokens
        finally:
            jax.device_get = real_get
        return eng, out, count["n"]

    eng_plain, toks_plain, _ = run_spec_stream(0)
    eng_spec, toks_spec, fetches_spec = run_spec_stream(spec_k)
    sstats = eng_spec.stats("spec")
    spec_exact = toks_spec == toks_plain
    if not spec_exact:
        problems.append(
            f"speculation changed greedy tokens: {toks_spec} != "
            f"{toks_plain}"
        )
    spec_budget = eng_spec.n_chains + eng_spec.n_prefills
    if fetches_spec > spec_budget:
        problems.append(
            f"spec arm: {fetches_spec} host fetches > {spec_budget} "
            f"({eng_spec.n_chains} chains + {eng_spec.n_prefills} "
            f"prefills)"
        )
    if sstats["spec_mean_accepted_len"] <= 1.0:
        problems.append(
            f"drafting never helped on a repetitive stream: {sstats}"
        )
    if sstats["n_verify_forwards"] >= eng_spec.generated_tokens:
        problems.append(
            f"{sstats['n_verify_forwards']} verify forwards >= "
            f"{eng_spec.generated_tokens} tokens emitted — speculation "
            f"saved no sequential steps"
        )

    # ------------------------------------------------------------------
    # multi-tenant adapter arm: N-1 tenants with distinct LoRA factors in
    # one bank; a mixed-tenant stream must match dedicated single-tenant
    # engines per request (one compiled program serves them all), id 0
    # must match the BANK-LESS base engine, the fetch budget is
    # unchanged, and unregistered ids are rejected at submit
    # ------------------------------------------------------------------
    bank = AdapterBank(model, n_adapters=adapters, rank=4)
    lparams = lora_init(
        bank.model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )["params"],
        jax.random.PRNGKey(5),
    )

    def fill_b(path, leaf):  # lora_init leaves B zero; tenants need deltas
        if str(getattr(path[-1], "key", path[-1])) != "lora_b":
            return leaf
        v = jax.random.normal(
            jax.random.PRNGKey(11), leaf.shape, leaf.dtype
        ) * 0.05
        return v.at[..., 0, :, :].set(0.0)

    lparams = jax.tree_util.tree_map_with_path(fill_b, lparams)
    base_row = extract_adapter(lparams, 1)
    for aid in range(1, adapters):
        # distinct factors per tenant (scaled copies — cheap, different)
        bank.register(f"tenant-{aid}", jax.tree_util.tree_map(
            lambda x, s=aid: x * (1.0 if s % 2 else -1.0) / s, base_row
        ))

    tenant_reqs = []  # (prompt, max_new, adapter id) — ids interleaved
    for i, (toks, max_new) in enumerate(prompts):
        tenant_reqs.append((toks, max_new, i % adapters))

    def run_tenant_stream(reqs, with_bank):
        eng = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8,
            adapter_bank=bank if with_bank else None,
        )
        count = {"n": 0}

        def counting(x):
            count["n"] += 1
            return real_get(x)

        jax.device_get = counting
        try:
            out = {}
            pending = list(reqs)
            for toks, max_new, aid in pending[:2]:
                eng.submit(Request(
                    prompt=toks, max_new_tokens=max_new, adapter=aid
                ))
            pending = pending[2:]
            while not eng.idle or pending:
                while pending:
                    toks, max_new, aid = pending[0]
                    try:
                        eng.submit(Request(
                            prompt=toks, max_new_tokens=max_new,
                            adapter=aid,
                        ))
                        pending.pop(0)
                    except QueueFull:
                        break
                for c in eng.step():
                    out[c.request_id] = c.tokens
        finally:
            jax.device_get = real_get
        return eng, out, count["n"]

    eng_mix, toks_mix, fetches_mix = run_tenant_stream(tenant_reqs, True)
    adapter_exact = True
    for aid in range(adapters):
        idx = [i for i, r in enumerate(tenant_reqs) if r[2] == aid]
        if not idx:
            continue
        solo_reqs = [tenant_reqs[i] for i in idx]
        _, toks_solo, _ = run_tenant_stream(solo_reqs, True)
        got = [toks_mix[i] for i in idx]
        want = [toks_solo[j] for j in sorted(toks_solo)]
        if got != want:
            adapter_exact = False
            problems.append(
                f"adapter {aid}: mixed-tenant tokens {got} != "
                f"dedicated-engine tokens {want}"
            )
    # id 0 through the bank == the bank-less base engine (zero factors
    # are EXACTLY the base model, not approximately)
    base_idx = [i for i, r in enumerate(tenant_reqs) if r[2] == 0]
    base_got = [toks_mix[i] for i in base_idx]
    base_want = [completions[i].tokens for i in base_idx]
    if base_got != base_want:
        adapter_exact = False
        problems.append(
            f"adapter 0 tokens {base_got} != base engine {base_want}"
        )
    mix_budget = eng_mix.n_chains + eng_mix.n_prefills
    if fetches_mix > mix_budget:
        problems.append(
            f"adapter arm: {fetches_mix} host fetches > {mix_budget} "
            f"({eng_mix.n_chains} chains + {eng_mix.n_prefills} prefills)"
        )
    try:
        eng_mix.submit(Request(
            prompt=[1, 2], max_new_tokens=2, adapter=adapters,
        ))
        problems.append(
            f"unregistered adapter id {adapters} admitted at submit"
        )
    except ValueError:
        pass
    astats = eng_mix.stats("adapters")
    if astats.get("adapter_requests", 0) < 1:
        problems.append(f"no tenant traffic recorded: {astats}")

    # ------------------------------------------------------------------
    # flight arm (--flight, ISSUE 10): the staggered base stream again,
    # now through a FlightRecorder-instrumented engine — tokens and the
    # fetch budget must be untouched (the recorder is host bookkeeping),
    # every completion must carry a FULL lifecycle span, per-stage event
    # counts must reconcile with the engine's counters, and the
    # streaming-histogram percentiles must match sort-based ones within
    # one bucket's documented relative error
    # ------------------------------------------------------------------
    flight_fields: dict = {}
    if flight:
        from pytorch_distributed_training_tutorials_tpu.obs import FlightRecorder

        rec = FlightRecorder(capacity=256)
        eng_f = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8, flight=rec
        )
        count = {"n": 0}

        def counting_f(x):
            count["n"] += 1
            return real_get(x)

        jax.device_get = counting_f
        try:
            comp_f = {}
            pending = list(prompts)
            for toks, max_new in pending[:2]:
                eng_f.submit(Request(prompt=toks, max_new_tokens=max_new))
            pending = pending[2:]
            while not eng_f.idle or pending:
                while pending:
                    toks, max_new = pending[0]
                    try:
                        eng_f.submit(
                            Request(prompt=toks, max_new_tokens=max_new)
                        )
                        pending.pop(0)
                    except QueueFull:
                        break
                for c in eng_f.step():
                    comp_f[c.request_id] = c
        finally:
            jax.device_get = real_get
        flight_budget = (
            eng_f.n_chains + eng_f.n_prefills + eng_f.n_splices
        )
        if count["n"] > flight_budget:
            problems.append(
                f"flight arm: {count['n']} host fetches > "
                f"{flight_budget} (recorder must cost zero fetches)"
            )
        if {r: c.tokens for r, c in comp_f.items()} != {
            r: c.tokens for r, c in completions.items()
        }:
            problems.append("flight recorder changed greedy tokens")
        spans = {s.get("rid"): s for s in rec.done_spans}
        span_keys = (
            "submit_t", "queue_pop_t", "prefill_t", "complete_t",
            "finish_reason",
        )
        span_full = len(spans) == len(prompts) and all(
            all(k in s for k in span_keys) for s in spans.values()
        )
        if not span_full:
            problems.append(
                f"flight arm: incomplete lifecycle spans: "
                f"{sorted(spans)} over {len(prompts)} requests"
            )
        kc = rec.kind_counts
        events_ok = (
            kc["submit"] == len(prompts)
            and kc["queue_pop"] == len(prompts)
            and kc["complete"] == len(prompts)
            and kc["prefill"] == eng_f.n_prefills
            and kc["chain_start"] == eng_f.n_chains
            and kc["chain_end"] == eng_f.n_chains
        )
        if not events_ok:
            problems.append(
                f"flight arm: event counts do not reconcile with the "
                f"engine counters: {dict(kc)} vs {eng_f.n_prefills} "
                f"prefills / {eng_f.n_chains} chains"
            )
        recon = all(
            abs(spans[r]["e2e_s"] - comp_f[r].latency_s) < 1e-5
            and abs(spans[r]["ttft_s"] - comp_f[r].ttft_s) < 1e-5
            for r in comp_f
        ) if span_full else False
        if span_full and not recon:
            problems.append(
                "flight arm: span timings diverge from Completion"
            )

        def hist_matches_sort(h, vals):
            # same rank convention as LogHistogram.quantile; the bound
            # is the histogram's own documented one-bucket error
            ok = True
            for q in (0.50, 0.95):
                sv = sorted(vals)[max(1, math.ceil(q * len(vals))) - 1]
                tol = h.rel_error_bound * max(sv, h.min_value) + 1e-9
                ok = ok and abs(h.quantile(q) - sv) <= tol
            return ok

        hist_ok = hist_matches_sort(
            rec.hist["e2e"], [c.latency_s for c in comp_f.values()]
        ) and hist_matches_sort(
            rec.hist["ttft"], [c.ttft_s for c in comp_f.values()]
        )
        if not hist_ok:
            problems.append(
                "flight arm: histogram p50/p95 outside one bucket of "
                "the sort-based percentiles"
            )
        flight_fields = {
            "flight_requests": len(prompts),
            "flight_span_full": span_full,
            "flight_events_consistent": events_ok,
            "flight_hist_vs_sort": hist_ok,
            "flight_host_fetches": count["n"],
            **eng_f.stats("flight"),
        }

    # ------------------------------------------------------------------
    # pipeline arm (--pipeline, ISSUE 11): the staggered base stream
    # again, now through a depth-2 double-buffered engine with chunked
    # prefill — tokens must stay byte-identical to the serial engine
    # (the pipeline only moves the fetch off the critical path), the
    # fetch budget is unchanged (mid-chunks are pure dispatch), and
    # chunking must visibly fire on the stream's 12-token prompt
    # ------------------------------------------------------------------
    pipeline_fields: dict = {}
    if pipeline:
        eng_p = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8,
            pipeline_depth=2, prefill_chunk=8,
        )
        count = {"n": 0}

        def counting_p(x):
            count["n"] += 1
            return real_get(x)

        jax.device_get = counting_p
        try:
            comp_p = {}
            pending = list(prompts)
            for toks, max_new in pending[:2]:
                eng_p.submit(Request(prompt=toks, max_new_tokens=max_new))
            pending = pending[2:]
            while not eng_p.idle or pending:
                while pending:
                    toks, max_new = pending[0]
                    try:
                        eng_p.submit(
                            Request(prompt=toks, max_new_tokens=max_new)
                        )
                        pending.pop(0)
                    except QueueFull:
                        break
                for c in eng_p.step():
                    comp_p[c.request_id] = c
        finally:
            jax.device_get = real_get
        pipeline_exact = {r: c.tokens for r, c in comp_p.items()} == {
            r: c.tokens for r, c in completions.items()
        }
        if not pipeline_exact:
            problems.append(
                "pipelined engine changed greedy tokens vs serial"
            )
        p_budget = eng_p.n_chains + eng_p.n_prefills + eng_p.n_splices
        if count["n"] > p_budget:
            problems.append(
                f"pipeline arm: {count['n']} host fetches > {p_budget} "
                f"({eng_p.n_chains} chains + {eng_p.n_prefills} prefills "
                f"+ {eng_p.n_splices} splices — chunks must add none)"
            )
        pstats = eng_p.stats("pipeline")
        if pstats.get("n_chunks", 0) < 1:
            problems.append(
                f"chunked prefill never fired on a 12-token prompt with "
                f"an 8-token chunk: {pstats}"
            )
        pipeline_fields = {
            "pipeline_requests": len(prompts),
            "pipeline_token_exact": pipeline_exact,
            "pipeline_host_fetches": count["n"],
            **pstats,
        }

    # ------------------------------------------------------------------
    # paged arm (--paged, ISSUE 13): a short+long mixed stream at
    # OVERSUBSCRIBED slot count (3 slots x 64-token windows = 192
    # claimable tokens over a 6-page x 8-token pool = 48) — admission is
    # by PAGES, tokens must stay byte-identical to the whole-slot
    # engine, the fetch budget is unchanged, a request that can never
    # fit the pool sheds synchronously at submit, and a prefix-cache
    # leg must show page SHARES (retained prefix pages seeding new
    # requests copy-free) while staying byte-identical to cache-off
    # ------------------------------------------------------------------
    paged_fields: dict = {}
    if paged:
        from pytorch_distributed_training_tutorials_tpu.serve import PoolExhausted

        paged_reqs = []
        for i, (p_len, max_new) in enumerate(
            [(3, 9), (17, 12), (5, 5), (12, 6), (2, 17), (9, 14)]
        ):
            rng, sub = jax.random.split(rng)
            paged_reqs.append((
                jax.device_get(jax.random.randint(
                    sub, (p_len,), 0, cfg.vocab_size
                )).tolist(),
                max_new,
            ))

        def run_paged_stream(reqs, prefix_bytes=0, page_kw=None):
            eng = ServeEngine(
                model, params, n_slots=3, tokens_per_launch=8,
                prefix_cache_bytes=prefix_bytes, **(page_kw or {}),
            )
            count = {"n": 0}

            def counting(x):
                count["n"] += 1
                return real_get(x)

            jax.device_get = counting
            try:
                out = {}
                pending = list(reqs)
                for toks, max_new in pending[:3]:
                    eng.submit(Request(prompt=toks, max_new_tokens=max_new))
                pending = pending[3:]
                while not eng.idle or pending:
                    while pending:
                        toks, max_new = pending[0]
                        try:
                            eng.submit(Request(
                                prompt=toks, max_new_tokens=max_new
                            ))
                            pending.pop(0)
                        except QueueFull:
                            break
                    for c in eng.step():
                        out[c.request_id] = c.tokens
            finally:
                jax.device_get = real_get
            return eng, out, count["n"]

        geometry = dict(paged=True, page_size=8, pool_pages=6)
        eng_ws, toks_ws, _ = run_paged_stream(paged_reqs)
        eng_pg, toks_pg, fetches_pg = run_paged_stream(
            paged_reqs, page_kw=geometry
        )
        paged_exact = toks_pg == toks_ws
        if not paged_exact:
            problems.append(
                f"paged engine changed greedy tokens: {toks_pg} != "
                f"{toks_ws}"
            )
        pg_budget = eng_pg.n_chains + eng_pg.n_prefills
        if fetches_pg > pg_budget:
            problems.append(
                f"paged arm: {fetches_pg} host fetches > {pg_budget} "
                f"({eng_pg.n_chains} chains + {eng_pg.n_prefills} "
                f"prefills)"
            )
        # a request that can never fit the 48-token pool (but WOULD fit
        # the 64-token window) must shed synchronously at submit
        paged_shed = False
        try:
            eng_pg.submit(Request(
                prompt=paged_reqs[1][0] * 2, max_new_tokens=30
            ))
            problems.append("pool-exceeding request admitted at submit")
        except PoolExhausted:
            paged_shed = True
        pgstats = eng_pg.stats("pages")
        if pgstats.get("pages_high_water", 0) < 1:
            problems.append(f"paged arm: pool never allocated: {pgstats}")
        if pgstats.get("pages_in_use", -1) != 0:
            problems.append(
                f"paged arm: {pgstats.get('pages_in_use')} pages leaked "
                f"after the stream drained"
            )
        # prefix leg: the overlapping stream through a paged cache-on
        # engine — tokens must match the (whole-slot) cache-off arm, and
        # the retained prefix pages must be SHARED, not copied
        eng_px, toks_px, fetches_px = run_paged_stream(
            overlap_reqs, prefix_bytes=16 * 1024 * 1024,
            page_kw=dict(paged=True, page_size=8, pool_pages=16),
        )
        paged_prefix_exact = toks_px == toks_off
        if not paged_prefix_exact:
            problems.append(
                f"paged prefix leg changed greedy tokens: {toks_px} != "
                f"{toks_off}"
            )
        px_budget = eng_px.n_chains + eng_px.n_prefills + eng_px.n_splices
        if fetches_px > px_budget:
            problems.append(
                f"paged prefix leg: {fetches_px} host fetches > "
                f"{px_budget} (chains + prefills + splices)"
            )
        pxstats = eng_px.stats("pages")
        if pxstats.get("pages_shares", 0) < 1:
            problems.append(
                f"paged prefix leg: no page shares on an overlapping "
                f"stream: {pxstats}"
            )
        # kernel leg (ISSUE 17): the same stream through the fused
        # Pallas page-walk read path — full-precision greedy must be
        # token-exact to the gather engine (and so to whole-slot), at
        # the unchanged fetch budget
        eng_kn, toks_kn, fetches_kn = run_paged_stream(
            paged_reqs, page_kw=dict(paged_kernel=True, **geometry),
        )
        kernel_exact = toks_kn == toks_ws
        if not kernel_exact:
            problems.append(
                f"paged kernel changed greedy tokens: {toks_kn} != "
                f"{toks_ws}"
            )
        kn_budget = eng_kn.n_chains + eng_kn.n_prefills
        if fetches_kn > kn_budget:
            problems.append(
                f"paged kernel leg: {fetches_kn} host fetches > "
                f"{kn_budget} (chains + prefills)"
            )
        # int4 leg (ISSUE 17): packed-nibble KV halves page_bytes
        # EXACTLY (bf16 scales: d/2 + 2 vs d + 4 per token-head), so
        # 2x the pages fit the int8 pool's HBM — the stream must still
        # complete every request (int4 rounding moves near-tie tokens,
        # so no exactness pin vs full precision) within budget
        eng_i8, _, _ = run_paged_stream(
            paged_reqs, page_kw=dict(kv_bits=8, **geometry),
        )
        eng_i4, toks_i4, fetches_i4 = run_paged_stream(
            paged_reqs,
            page_kw=dict(
                kv_bits=4, paged_kernel=True, paged=True,
                page_size=8, pool_pages=12,
            ),
        )
        pb8 = eng_i8.page_stats()["page_bytes"]
        pb4 = eng_i4.page_stats()["page_bytes"]
        int4_halved = pb4 * 2 == pb8
        if not int4_halved:
            problems.append(
                f"int4 page_bytes {pb4} is not exactly half of int8's "
                f"{pb8}"
            )
        int4_ok = (
            len(toks_i4) == len(paged_reqs)
            and all(
                len(toks_i4[rid]) > 0 for rid in toks_i4
            )
            and fetches_i4 <= eng_i4.n_chains + eng_i4.n_prefills
        )
        if not int4_ok:
            problems.append(
                f"int4 kernel leg incomplete or over budget: "
                f"{len(toks_i4)} completions, {fetches_i4} fetches"
            )
        paged_fields = {
            "paged_requests": len(paged_reqs),
            "paged_token_exact": paged_exact,
            "paged_host_fetches": fetches_pg,
            "paged_shed_ok": paged_shed,
            "paged_prefix_token_exact": paged_prefix_exact,
            "paged_prefix_shares": pxstats.get("pages_shares", 0),
            "paged_kernel_token_exact": kernel_exact,
            "paged_int4_page_bytes_halved": int4_halved,
            "paged_int4_ok": int4_ok,
            "paged_int4_pool_pages": 12,
            **pgstats,
        }

    # ------------------------------------------------------------------
    # router arm (--router, ISSUE 12): a 3-replica fleet of REAL engines
    # behind the FleetRouter. Leg 1 (fault-free) pins fleet == single
    # engine: every request's greedy tokens byte-identical to the base
    # arm's. Leg 2 re-runs the same stream with a chaos-killed replica
    # mid-stream: the DispatchLedger must verify exactly-once (no
    # accepted request lost or completed twice), every request that
    # still finished "length" must be byte-identical to the fault-free
    # run (re-dispatch is invisible in outputs — same template, same
    # seed), and the summed per-replica fetch budget stays exactly
    # chains + prefills + splices. The fleet flight summary (merged
    # histograms, shared t0) rides into the receipt.
    # ------------------------------------------------------------------
    router_fields: dict = {}
    if router:
        import time as _time

        from pytorch_distributed_training_tutorials_tpu.obs import FlightRecorder
        from pytorch_distributed_training_tutorials_tpu.serve import FleetRouter, affinity_hash
        from pytorch_distributed_training_tutorials_tpu.utils.chaos import FleetChaosConfig

        n_replicas = 3
        # the base stream plus two same-prompt clones of request 0, so
        # the kill target (request 0's affine replica) is guaranteed to
        # hold BOTH in-flight and queued work when it dies
        fleet_stream = list(prompts) + [prompts[0], prompts[0]]
        expected_gid = {g: completions[g].tokens for g in range(len(prompts))}
        expected_gid[len(prompts)] = completions[0].tokens
        expected_gid[len(prompts) + 1] = completions[0].tokens
        kill_target = affinity_hash(prompts[0][0], adapter=0,
                                    depth=16) % n_replicas

        def run_fleet(fleet_chaos):
            t0 = _time.perf_counter()
            engines = [
                ServeEngine(
                    model, params, n_slots=1, tokens_per_launch=4,
                    max_queue=8,
                    flight=FlightRecorder(capacity=256, t0=t0),
                )
                for _ in range(n_replicas)
            ]
            fr = FleetRouter(
                engines, chaos=fleet_chaos,
                flight=FlightRecorder(capacity=256, t0=t0),
            )
            count = {"n": 0}

            def counting(x):
                count["n"] += 1
                return real_get(x)

            jax.device_get = counting
            try:
                out = {}
                for toks, max_new in fleet_stream:
                    fr.submit(Request(prompt=toks, max_new_tokens=max_new))
                for c in fr.run_until_idle():
                    out[c.request_id] = c
            finally:
                jax.device_get = real_get
            return fr, engines, out, count["n"]

        # leg 1: fault-free fleet — byte-identical to the single engine
        fr_ok, eng_ok, out_ok, fetches_ok = run_fleet(None)
        fleet_exact = all(
            out_ok[g].tokens == expected_gid[g]
            and out_ok[g].finish_reason == "length"
            for g in expected_gid
        )
        if len(out_ok) != len(fleet_stream) or not fleet_exact:
            problems.append(
                f"fault-free fleet diverged from the single engine: "
                f"{[(g, c.finish_reason) for g, c in sorted(out_ok.items())]}"
            )
        ledger_ok = fr_ok.ledger.verify()
        if ledger_ok:
            problems.append(f"fault-free fleet ledger: {ledger_ok}")

        # leg 2: same stream, one replica chaos-killed mid-stream
        fr_x, eng_x2, out_x, fetches_x = run_fleet(FleetChaosConfig(
            kill_replica=kill_target, kill_at_chain=2,
        ))
        if len(out_x) != len(fleet_stream):
            problems.append(
                f"chaos fleet: {len(out_x)} completions for "
                f"{len(fleet_stream)} accepted requests"
            )
        ledger_x = fr_x.ledger.verify()
        if ledger_x:
            problems.append(f"chaos fleet ledger: {ledger_x}")
        if fr_x.replica_states()[kill_target] != "dead":
            problems.append(
                f"killed replica {kill_target} is "
                f"{fr_x.replica_states()[kill_target]!r}, expected dead"
            )
        moved = fr_x.ledger.n_redispatched + fr_x.n_dead_completions
        if moved < 1:
            problems.append(
                "chaos fleet: the killed replica held no work — the "
                "re-dispatch path never fired"
            )
        router_exact = all(
            c.tokens == expected_gid[g]
            for g, c in out_x.items()
            if c.finish_reason in ("length", "eos")
        )
        if not router_exact:
            problems.append(
                "chaos fleet: a re-dispatched request's tokens diverged "
                "from the fault-free run"
            )
        # summed per-replica fetch budget: the killed engine's counters
        # freeze at the kill (the router never steps it again)
        fleet_budget = sum(
            e.n_chains + e.n_prefills + e.n_splices for e in eng_x2
        )
        if fetches_x > fleet_budget:
            problems.append(
                f"chaos fleet: {fetches_x} host fetches > {fleet_budget} "
                f"(sum of per-replica chains + prefills + splices)"
            )
        rstats = fr_x.stats()
        if (fr_x.fleet_flight_summary() or {}).get("e2e_count", 0) < 1:
            problems.append("fleet flight summary recorded no requests")
        router_fields = {
            "router_requests": len(fleet_stream),
            "router_fleet_exact": fleet_exact and router_exact,
            "router_host_fetches_ok": fetches_ok,
            "router_host_fetches_chaos": fetches_x,
            "router_killed_replica": kill_target,
            **{f"router_{k}": v for k, v in rstats.items()
               if isinstance(v, (int, float, bool))},
        }

    # ------------------------------------------------------------------
    # chaos arm (--chaos, ISSUE 9): one staggered stream exercising every
    # serving failure path — injected NaN logits (quarantine), a deadline
    # expiry, a host-side cancel, close/drain — with the fetch budget
    # still counted, co-scheduled requests still token-identical to a
    # clean run; plus a mini training leg driving the skip-step guard
    # (poisoned batch -> state bitwise unchanged, counter increments)
    # ------------------------------------------------------------------
    fault_fields: dict = {}
    if chaos:
        import optax

        from pytorch_distributed_training_tutorials_tpu.models import (
            LinearRegressor,
        )
        from pytorch_distributed_training_tutorials_tpu.serve import (
            QueueClosed,
        )
        from pytorch_distributed_training_tutorials_tpu.train.trainer import (
            TrainState,
            make_train_step,
        )
        from pytorch_distributed_training_tutorials_tpu.utils import (
            chaos as chaos_lib,
        )

        p0, p1 = prompts[0][0], prompts[1][0]
        ccfg = chaos_lib.ChaosConfig(nan_logit_slot=0, nan_logit_step=3)

        # clean reference (guard on, NO faults) for token-identity
        eng_ref = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=4,
            guard_nonfinite=True,
        )
        eng_ref.submit(Request(prompt=p0, max_new_tokens=12))
        eng_ref.submit(Request(prompt=p1, max_new_tokens=16))
        ref = {c.request_id: c for c in eng_ref.run_until_idle()}

        # the faulty engine carries a dump-path recorder: every injected
        # fault must auto-dump a graft-flightlog/v1 snapshot whose
        # trigger names the victim (the ISSUE 10 post-mortem contract)
        from pytorch_distributed_training_tutorials_tpu.obs import (
            FlightRecorder,
            load_flightlog,
        )

        fd, dump_path = tempfile.mkstemp(suffix=".flightlog.jsonl")
        os.close(fd)
        eng_x = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=4,
            guard_nonfinite=True, chaos=ccfg,
            flight=FlightRecorder(capacity=128, dump_path=dump_path),
        )
        count = {"n": 0}

        def counting(x):
            count["n"] += 1
            return real_get(x)

        jax.device_get = counting
        try:
            r0 = eng_x.submit(Request(prompt=p0, max_new_tokens=12))
            r1 = eng_x.submit(Request(prompt=p1, max_new_tokens=16))
            r2 = eng_x.submit(
                Request(prompt=p0, max_new_tokens=8, deadline_s=1e-6)
            )
            r3 = eng_x.submit(Request(prompt=p1, max_new_tokens=8))
            eng_x.cancel(r3)
            out = {c.request_id: c for c in eng_x.drain()}
        finally:
            jax.device_get = real_get
        chaos_fetches = count["n"]
        try:
            eng_x.submit(Request(prompt=p0, max_new_tokens=2))
            problems.append("submit admitted after close()")
        except QueueClosed:
            pass
        if out[r0].finish_reason != "nonfinite":
            problems.append(
                f"poisoned slot finished {out[r0].finish_reason!r}, "
                "expected 'nonfinite'"
            )
        chaos_exact = (
            out[r0].tokens == ref[0].tokens[: len(out[r0].tokens)]
            and len(out[r0].tokens) < len(ref[0].tokens)
            and out[r1].tokens == ref[1].tokens
        )
        if not chaos_exact:
            problems.append(
                f"chaos arm tokens diverged from clean run: poisoned "
                f"{out[r0].tokens} vs clean {ref[0].tokens}, co-scheduled "
                f"{out[r1].tokens} vs {ref[1].tokens}"
            )
        if out[r2].finish_reason != "deadline" or out[r2].tokens:
            problems.append(
                f"deadline request finished {out[r2].finish_reason!r} "
                f"with {len(out[r2].tokens)} tokens"
            )
        if out[r3].finish_reason != "cancelled":
            problems.append(
                f"cancelled request finished {out[r3].finish_reason!r}"
            )
        chaos_budget = eng_x.n_chains + eng_x.n_prefills + eng_x.n_splices
        if chaos_fetches > chaos_budget:
            problems.append(
                f"chaos arm: {chaos_fetches} host fetches > "
                f"{chaos_budget} (chains + prefills + splices)"
            )
        fstats = eng_x.stats("fault")
        for key, want in (
            ("nonfinite_quarantined", 1),
            ("deadline_expired", 1),
            ("cancelled", 1),
        ):
            if fstats.get(key) != want:
                problems.append(
                    f"fault_stats[{key!r}] = {fstats.get(key)}, "
                    f"expected {want}"
                )
        # the flight dump: one snapshot per fault-class event, and the
        # nonfinite one must NAME the quarantined slot
        try:
            snaps = load_flightlog(dump_path)
        except ValueError as e:
            snaps = []
            problems.append(f"chaos flight dump failed validation: {e}")
        named_slot = any(
            s.get("trigger", {}).get("fault_kind") == "nonfinite"
            and s.get("trigger", {}).get("slot") == 0
            for s in snaps
            if s.get("trigger")
        )
        if len(snaps) < 2:  # nonfinite + deadline at minimum
            problems.append(
                f"chaos arm: {len(snaps)} flight dumps, expected >= 2 "
                "(nonfinite quarantine + deadline expiry)"
            )
        if not named_slot:
            problems.append(
                "chaos arm: no flight dump names the quarantined slot"
            )
        os.unlink(dump_path)

        # mini training leg: skip-step guard on a poisoned batch
        reg = LinearRegressor(in_dim=4)
        key = jax.random.PRNGKey(2)
        xb = jax.random.normal(key, (8, 4))
        yb = jnp.ones((8, 1), jnp.float32)
        st = TrainState.create(
            apply_fn=reg.apply,
            params=reg.init(key, xb)["params"],
            tx=optax.adam(1e-2),
        )
        gstep = make_train_step(loss="mse", skip_nonfinite=True)
        tcfg = chaos_lib.ChaosConfig(nan_batch_step=1)
        before = real_get((st.params, st.opt_state, st.step))
        st1, m1 = gstep(st, chaos_lib.maybe_poison_batch(tcfg, 1, (xb, yb)))
        after = real_get((st1.params, st1.opt_state, st1.step))
        st2, m2 = gstep(st1, chaos_lib.maybe_poison_batch(tcfg, 2, (xb, yb)))
        steps_skipped = int(real_get(m1["skipped"])) + int(
            real_get(m2["skipped"])
        )
        import numpy as np

        bitwise_skip = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree_util.tree_leaves(before),
                jax.tree_util.tree_leaves(after),
            )
        )
        if not bitwise_skip:
            problems.append(
                "skip-step left TrainState changed after a poisoned batch"
            )
        if steps_skipped != 1:
            problems.append(
                f"steps_skipped = {steps_skipped}, expected exactly 1 "
                "(poisoned batch skipped, clean batch applied)"
            )
        if int(real_get(st2.step)) != 1:
            problems.append("clean step after the skip did not apply")
        fault_fields = {
            **fstats,
            "steps_skipped": steps_skipped,
            "chaos_token_exact": chaos_exact,
            "chaos_host_fetches": chaos_fetches,
            "chaos_flight_dumps": len(snaps),
            "chaos_flight_named_slot": named_slot,
        }

    # ------------------------------------------------------------------
    # tp arm (--tp N, ISSUE 15): the base staggered stream through a
    # TensorParallel-sharded engine on a {'model': N} mesh. Greedy
    # tokens must be byte-identical to the replicated base arm (the
    # Megatron split is an implementation detail), the fetch budget is
    # unchanged (ONE batched device_get per chain regardless of mesh —
    # per-shard fetches would multiply the launch roundtrip by tp), the
    # KV cache must REALLY shard (per-chip bytes < global bytes), and
    # the compiled decode chain's HLO must contain no collectives
    # beyond the whitelisted all-reduces (audit_decode_hlo).
    # ------------------------------------------------------------------
    tp_fields: dict = {}
    if tp > 1:
        from pytorch_distributed_training_tutorials_tpu.models.transformer import TP_RULES
        from pytorch_distributed_training_tutorials_tpu.parallel import TensorParallel
        from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh

        if len(jax.devices()) < tp:
            problems.append(
                f"tp arm: {len(jax.devices())} devices < tp={tp}"
            )
        else:
            mesh = create_mesh({"model": tp})
            eng_tp = ServeEngine(
                model, params, n_slots=2, tokens_per_launch=8,
                strategy=TensorParallel(mesh, TP_RULES),
            )
            count_tp = {"n": 0}

            def counting_tp(x):
                count_tp["n"] += 1
                return real_get(x)

            jax.device_get = counting_tp
            try:
                toks_tp = {}
                pending = list(prompts)
                for toks, max_new in pending[:2]:
                    eng_tp.submit(
                        Request(prompt=toks, max_new_tokens=max_new)
                    )
                pending = pending[2:]
                while not eng_tp.idle or pending:
                    while pending:
                        toks, max_new = pending[0]
                        try:
                            eng_tp.submit(Request(
                                prompt=toks, max_new_tokens=max_new
                            ))
                            pending.pop(0)
                        except QueueFull:
                            break
                    for c in eng_tp.step():
                        toks_tp[c.request_id] = c.tokens
                fetches_tp = count_tp["n"]
            finally:
                jax.device_get = real_get
            tp_exact = all(
                toks_tp.get(rid) == completions[rid].tokens
                for rid in range(len(prompts))
            )
            if not tp_exact:
                problems.append(
                    f"tp={tp} engine changed greedy tokens: {toks_tp}"
                )
            tp_budget = eng_tp.n_chains + eng_tp.n_prefills
            if fetches_tp > tp_budget:
                problems.append(
                    f"tp arm: {fetches_tp} host fetches > {tp_budget} "
                    f"({eng_tp.n_chains} chains + {eng_tp.n_prefills} "
                    f"prefills) — a per-shard fetch leaked in"
                )
            audit = eng_tp.audit_decode_hlo()
            if not audit["ok"]:
                problems.append(
                    f"tp arm: unexpected collectives in the decode "
                    f"HLO: {audit['problems'][:3]}"
                )
            tpstats = eng_tp.stats("tp")
            from pytorch_distributed_training_tutorials_tpu.serve.slots import tree_nbytes
            global_kv = tree_nbytes(eng_tp._state["cache"])
            if tpstats.get("tp_kv_bytes_per_chip", global_kv) >= global_kv:
                problems.append(
                    f"tp arm: per-chip KV bytes "
                    f"{tpstats.get('tp_kv_bytes_per_chip')} not below "
                    f"global {global_kv} — the cache never sharded"
                )
            tp_fields = {
                "tp_requests": len(prompts),
                "tp_token_exact": tp_exact,
                "tp_host_fetches": fetches_tp,
                "tp_kv_bytes_global": global_kv,
                **tpstats,
            }

    # ------------------------------------------------------------------
    # contract-sentry arm (ISSUE 19): the runtime twin of this harness's
    # own monkeypatch spies. A sentry-instrumented engine runs the base
    # stream clean (warmup, mark_steady, then a steady repeat that must
    # show ZERO steady recompiles, an exactly-balanced fetch budget, and
    # zero host-numpy re-uploads — with the sentry's counts equal to an
    # independent monkeypatch spy's). Then one injected violation per
    # probe class — a post-steady jit of a fresh program, a stray
    # device_get inside a step round, a host-numpy arg tree — must each
    # produce exactly one typed flight event and one graft-flightlog/v1
    # auto-dump naming its trigger.
    # ------------------------------------------------------------------
    sentry_fields: dict = {}
    if sentry:
        from pytorch_distributed_training_tutorials_tpu.obs import (
            ContractSentry,
            FlightRecorder,
            load_flightlog,
        )

        fd, sen_dump = tempfile.mkstemp(suffix=".flightlog.jsonl")
        os.close(fd)
        fl_sen = FlightRecorder(capacity=256, dump_path=sen_dump)
        sen = ContractSentry(flight=fl_sen)
        eng_sen = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8, max_queue=2,
            flight=fl_sen, sentry=sen,
        )
        count_sen = {"n": 0}

        def counting_sen(x):
            count_sen["n"] += 1
            return real_get(x)

        def run_sen_stream(collect):
            pending = list(prompts)
            for toks, max_new in pending[:2]:
                eng_sen.submit(Request(prompt=toks, max_new_tokens=max_new))
            pending = pending[2:]
            while not eng_sen.idle or pending:
                while pending:
                    toks, max_new = pending[0]
                    try:
                        eng_sen.submit(
                            Request(prompt=toks, max_new_tokens=max_new)
                        )
                        pending.pop(0)
                    except QueueFull:
                        break
                for c in eng_sen.step():
                    collect[c.request_id] = c.tokens

        # the spy goes UNDER the sentry wrapper: every fetch flows
        # sentry -> spy -> real, so the two counters must agree exactly
        jax.device_get = counting_sen
        sen.install()
        try:
            # warmup phase: every compiled program this stream needs
            run_sen_stream({})
            # prebuild the injection operands while compiles are still
            # legal — jnp.zeros/arange compile their own fill programs,
            # which must not pollute the steady-state count
            stray_scalar = jnp.zeros((), jnp.float32)
            fresh_arg = jnp.arange(11, dtype=jnp.float32)
            device_tree = {"w": jnp.ones((4, 4), jnp.float32)}
            sen.mark_steady()

            # steady clean leg: identical shapes, zero new programs
            toks_sen: dict = {}
            base_id = len(prompts)  # phase 1 consumed ids 0..N-1
            run_sen_stream(toks_sen)
            sen_exact = all(
                toks_sen.get(base_id + rid) == completions[rid].tokens
                for rid in range(len(prompts))
            )
            if not sen_exact:
                problems.append(
                    f"sentry arm: instrumented engine changed greedy "
                    f"tokens: {toks_sen}"
                )
            if sen.n_steady_recompiles:
                problems.append(
                    f"sentry arm: {sen.n_steady_recompiles} steady "
                    f"recompiles on a shape-identical repeat stream"
                )
            if sen.n_budget_violations:
                problems.append(
                    f"sentry arm: {sen.n_budget_violations} budget "
                    "violations on the clean stream"
                )
            sen_budget = eng_sen.n_chains + eng_sen.n_prefills
            if not (sen.n_fetched == count_sen["n"]
                    == sen.n_budgeted == sen_budget):
                problems.append(
                    f"sentry arm: fetch accounting disagrees — sentry "
                    f"{sen.n_fetched} fetched / {sen.n_budgeted} "
                    f"budgeted, spy {count_sen['n']}, engine budget "
                    f"{sen_budget}"
                )
            clean_summary = dict(sen.summary())

            # violation leg 1: a post-steady compilation (fresh program
            # over a PREBUILT operand) — exactly one steady recompile
            jax.jit(lambda v: v * 3.0 + 1.0)(fresh_arg)
            recompile_caught = sen.n_steady_recompiles == 1
            if not recompile_caught:
                problems.append(
                    f"sentry arm: injected recompile counted "
                    f"{sen.n_steady_recompiles} times (want 1; "
                    f"probe={sen.compile_probe})"
                )

            # violation leg 2: a stray un-budgeted device_get inside ONE
            # step round (the leak the fetch-budget rule exists to stop)
            orig_sweep = eng_sen._sweep

            def leaky_sweep():
                jax.device_get(stray_scalar)
                return orig_sweep()

            eng_sen.submit(Request(prompt=prompts[0][0], max_new_tokens=3))
            eng_sen._sweep = leaky_sweep
            eng_sen.step()  # exactly one over-budget round
            eng_sen._sweep = orig_sweep
            while not eng_sen.idle:
                eng_sen.step()
            budget_caught = sen.n_budget_violations == 1
            if not budget_caught:
                problems.append(
                    f"sentry arm: injected stray fetch flagged "
                    f"{sen.n_budget_violations} rounds (want 1)"
                )

            # violation leg 3: host-numpy leaves in an arg tree fire the
            # re-upload probe; the device-resident twin stays silent
            import numpy as np
            sen.check_args(
                {"w": np.ones((4, 4), np.float32)}, label="selftest_numpy"
            )
            clean_bytes = sen.check_args(device_tree, label="selftest_numpy")
            reupload_caught = sen.n_reuploads == 1 and clean_bytes == 0
            if not reupload_caught:
                problems.append(
                    f"sentry arm: reupload probe saw {sen.n_reuploads} "
                    f"hits / {clean_bytes} B on the device twin "
                    "(want 1 / 0)"
                )
        finally:
            sen.uninstall()
            jax.device_get = real_get

        # each injected violation class = one auto-dump naming its
        # trigger (the chaos-arm contract, extended to the sentry kinds)
        try:
            snaps = load_flightlog(sen_dump)
        except ValueError as e:
            snaps = []
            problems.append(f"sentry flight dump failed validation: {e}")
        by_reason: dict = {}
        for s in snaps:
            by_reason.setdefault(s["reason"], []).append(s)
        for reason, check in (
            ("compile", lambda t: t.get("steady") is True),
            ("budget_violation",
             lambda t: t.get("fetched", 0) > t.get("budgeted", 0)),
            ("reupload", lambda t: t.get("label") == "selftest_numpy"),
        ):
            got = by_reason.get(reason, [])
            if len(got) != 1:
                problems.append(
                    f"sentry arm: {len(got)} '{reason}' dumps (want "
                    "exactly 1)"
                )
            elif not check(got[0].get("trigger") or {}):
                problems.append(
                    f"sentry arm: '{reason}' dump trigger does not name "
                    f"its violation: {got[0].get('trigger')}"
                )
        os.unlink(sen_dump)
        sentry_fields = {
            **clean_summary,
            "sentry_token_exact": sen_exact,
            "sentry_injected_recompile_caught": recompile_caught,
            "sentry_injected_budget_caught": budget_caught,
            "sentry_injected_reupload_caught": reupload_caught,
            "sentry_dump_snapshots": len(snaps),
        }

    # ------------------------------------------------------------------
    # slo arm (--slo, ISSUE 20): priority scheduling + preemption by KV
    # swap. A 1-slot priority engine decoding a low-class request must
    # preempt for an arriving class-0 request (swap the victim's cache
    # segment to host — the counted swap fetch), serve the interactive
    # request, swap the victim back in, and finish BOTH token-exact to
    # generate(). Budget = chains + prefills + splices + swaps, pinned
    # by the monkeypatch spy AND a ContractSentry riding the stream.
    # Chaos leg: preempt_at_chain force-preempts with no pressure; the
    # victim resumes token-exact and the co-scheduled slot is
    # byte-identical to a clean run. Host leg: single-class
    # PriorityScheduler pop order == FifoScheduler.
    # ------------------------------------------------------------------
    slo_fields: dict = {}
    if slo:
        from pytorch_distributed_training_tutorials_tpu.obs import ContractSentry
        from pytorch_distributed_training_tutorials_tpu.serve.scheduler import FifoScheduler
        from pytorch_distributed_training_tutorials_tpu.serve.slo import PriorityScheduler
        from pytorch_distributed_training_tutorials_tpu.utils.chaos import ChaosConfig

        lo_toks, lo_new = prompts[4]   # (2, 17): 3 chains of decode
        hi_toks, hi_new = prompts[3]   # (12, 6): the interactive burst

        def one_shot(toks, max_new):
            return jax.device_get(
                generate(
                    model, params, jnp.asarray([toks], jnp.int32), max_new
                )
            )[0, len(toks):].tolist()

        lo_ref = one_shot(lo_toks, lo_new)
        hi_ref = one_shot(hi_toks, hi_new)

        sen_slo = ContractSentry()
        eng_slo = ServeEngine(
            model, params, n_slots=1, tokens_per_launch=8,
            priority_classes=2, sentry=sen_slo,
        )
        count_slo = {"n": 0}

        def counting_slo(x):
            count_slo["n"] += 1
            return real_get(x)

        # spy goes UNDER the sentry wrapper (sentry -> spy -> real), the
        # same layering the --sentry arm uses, so both counters see
        # every fetch including the swap-out's
        jax.device_get = counting_slo
        sen_slo.install()
        try:
            slo_done = {}
            lo_req = Request(prompt=list(lo_toks), max_new_tokens=lo_new,
                             priority=1)
            eng_slo.submit(lo_req)
            for c in eng_slo.step():   # prefill + first chain (9 of 17)
                slo_done[c.request_id] = c
            hi_req = Request(prompt=list(hi_toks), max_new_tokens=hi_new,
                             priority=0)
            eng_slo.submit(hi_req)
            while not eng_slo.idle:
                for c in eng_slo.step():
                    slo_done[c.request_id] = c
            slo_fetches = count_slo["n"]
        finally:
            sen_slo.uninstall()
            jax.device_get = real_get
        if eng_slo.n_swaps_out < 1 or eng_slo.n_swaps_in < 1:
            problems.append(
                f"slo arm: no preemption fired (swaps out "
                f"{eng_slo.n_swaps_out} / in {eng_slo.n_swaps_in})"
            )
        slo_exact = (
            slo_done[lo_req.request_id].tokens == lo_ref
            and slo_done[hi_req.request_id].tokens == hi_ref
        )
        if not slo_exact:
            problems.append(
                f"slo arm: preemption changed greedy tokens — lo "
                f"{slo_done[lo_req.request_id].tokens} vs {lo_ref}, hi "
                f"{slo_done[hi_req.request_id].tokens} vs {hi_ref}"
            )
        slo_budget = (
            eng_slo.n_chains + eng_slo.n_prefills + eng_slo.n_splices
            + eng_slo.n_swaps_out
        )
        if slo_fetches > slo_budget:
            problems.append(
                f"slo arm: {slo_fetches} host fetches > {slo_budget} "
                f"({eng_slo.n_chains} chains + {eng_slo.n_prefills} "
                f"prefills + {eng_slo.n_splices} splices + "
                f"{eng_slo.n_swaps_out} swaps)"
            )
        # the sentry's round accounting is the same claim at runtime:
        # every swap fetch flowed through the budgeted _sentry_fetch
        # seam, so no round closed with fetched > budgeted
        if sen_slo.n_budget_violations:
            problems.append(
                f"slo arm: {sen_slo.n_budget_violations} sentry budget "
                f"violations — a swap fetch escaped the budgeted seam"
            )
        if sen_slo.n_fetched != sen_slo.n_budgeted:
            problems.append(
                f"slo arm: sentry fetched {sen_slo.n_fetched} != "
                f"budgeted {sen_slo.n_budgeted}"
            )

        # chaos leg: forced preempt with NO pressure — the co-scheduled
        # slot must be byte-identical to a clean 2-slot run
        clean2 = ServeEngine(model, params, n_slots=2, tokens_per_launch=8)
        a_req = Request(prompt=list(lo_toks), max_new_tokens=lo_new)
        b_req = Request(prompt=list(hi_toks), max_new_tokens=hi_new)
        clean2.submit(a_req)
        clean2.submit(b_req)
        clean_out = {c.request_id: c.tokens for c in clean2.run_until_idle()}
        chaos2 = ServeEngine(
            model, params, n_slots=2, tokens_per_launch=8,
            priority_classes=2,
            chaos=ChaosConfig(preempt_slot=0, preempt_at_chain=1),
        )
        a2 = Request(prompt=list(lo_toks), max_new_tokens=lo_new, priority=1)
        b2 = Request(prompt=list(hi_toks), max_new_tokens=hi_new, priority=1)
        chaos2.submit(a2)
        chaos2.submit(b2)
        chaos_out = {c.request_id: c.tokens
                     for c in chaos2.run_until_idle()}
        if chaos2.n_swaps_out != 1:
            problems.append(
                f"slo arm: chaos preempt fired {chaos2.n_swaps_out} "
                "times (want exactly 1)"
            )
        chaos_exact = (
            chaos_out[a2.request_id] == clean_out[a_req.request_id]
            and chaos_out[b2.request_id] == clean_out[b_req.request_id]
        )
        if not chaos_exact:
            problems.append(
                f"slo arm: forced preempt changed tokens — "
                f"{chaos_out} vs clean {clean_out}"
            )

        # host leg: single-class PriorityScheduler pop order ==
        # FifoScheduler over the same submissions (jax-free)
        fifo = FifoScheduler(64, max_queue=16)
        single = PriorityScheduler(64, max_queue=16, n_classes=1)
        for p_len in (3, 7, 5, 12, 2):
            fifo.submit(Request(prompt=[1] * p_len, max_new_tokens=4))
            single.submit(Request(prompt=[1] * p_len, max_new_tokens=4))
        fifo_order = []
        single_order = []
        while True:
            f, s = fifo.pop(), single.pop()
            if f is None and s is None:
                break
            fifo_order.append(None if f is None else f.request_id)
            single_order.append(None if s is None else s.request_id)
        if fifo_order != single_order:
            problems.append(
                f"slo arm: single-class PriorityScheduler order "
                f"{single_order} != FIFO {fifo_order}"
            )

        slo_fields = {
            **eng_slo.stats("slo"),
            "slo_token_exact": slo_exact,
            "slo_chaos_token_exact": chaos_exact,
            "slo_host_fetches": slo_fetches,
            "slo_fetch_budget": slo_budget,
            "slo_single_class_fifo_identical": fifo_order == single_order,
        }

    receipt = make_receipt(
        "serve_selftest",
        {
            "n_requests": len(prompts),
            "n_slots": 2,
            "tokens_per_launch": 8,
            "n_chains": n_chains,
            "n_prefills": engine.n_prefills,
            "host_fetches": n_fetch,
            "generated_tokens": engine.generated_tokens,
            "token_exact_mismatches": mismatches,
            "backpressure_seen": backpressured,
            "prefix_requests": len(overlap_reqs),
            "prefix_token_exact": prefix_exact,
            "prefix_prefills_off": eng_off.n_prefills,
            "prefix_prefills_on": eng_on.n_prefills,
            **stats,
            "spec_requests": len(spec_reqs),
            "spec_token_exact": spec_exact,
            "spec_generated_tokens": eng_spec.generated_tokens,
            "spec_host_fetches": fetches_spec,
            **sstats,
            "adapter_requests_total": len(tenant_reqs),
            "adapter_token_exact": adapter_exact,
            "adapter_host_fetches": fetches_mix,
            **astats,
            **flight_fields,
            **pipeline_fields,
            **paged_fields,
            **router_fields,
            **fault_fields,
            **tp_fields,
            **sentry_fields,
            **slo_fields,
            "problems": problems,
            "ok": not problems,
        },
    )
    problems.extend(validate_receipt(receipt, kind="serve_selftest"))
    receipt["ok"] = not problems
    receipt["problems"] = problems
    if json_path:
        with open(json_path, "w") as f:
            json.dump(receipt, f, indent=2)
            f.write("\n")
    return receipt


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="python -m pytorch_distributed_training_tutorials_tpu.serve")
    parser.add_argument(
        "--selftest", action="store_true",
        help="run the end-to-end continuous-batching smoke test",
    )
    parser.add_argument(
        "--json", default=None, help="also write the receipt to this path"
    )
    parser.add_argument(
        "--spec-k", type=int, default=2,
        help="speculate-k for the speculative selftest arm (>= 1)",
    )
    parser.add_argument(
        "--adapters", type=int, default=3,
        help="bank rows for the multi-tenant selftest arm (>= 2; "
        "rows 1..N-1 become tenants, row 0 is the base model)",
    )
    parser.add_argument(
        "--chaos", action="store_true",
        help="also run the fault-injection arm: NaN-logit quarantine, "
        "deadline expiry, cancel, close/drain, and the training "
        "skip-step guard (ISSUE 9)",
    )
    parser.add_argument(
        "--flight", action="store_true",
        help="also run the flight-recorder arm: full lifecycle spans, "
        "histogram-vs-sort percentile parity, unchanged fetch budget "
        "(ISSUE 10)",
    )
    parser.add_argument(
        "--pipeline", action="store_true",
        help="also run the pipelined arm: depth-2 double-buffered "
        "chains + chunked prefill, token-identical to serial with the "
        "same fetch budget (ISSUE 11)",
    )
    parser.add_argument(
        "--router", action="store_true",
        help="also run the fleet arm: 3 real-engine replicas behind "
        "FleetRouter, fault-free parity vs the single engine, then a "
        "chaos-killed replica mid-stream with the exactly-once ledger, "
        "token-exact re-dispatch, and the summed per-replica fetch "
        "budget asserted (ISSUE 12)",
    )
    parser.add_argument(
        "--paged", action="store_true",
        help="also run the paged-KV arm: an oversubscribed mixed stream "
        "through a page-pool engine, token-identical to whole-slot with "
        "the same fetch budget, PoolExhausted shed at submit, and "
        "copy-free page sharing under the prefix cache (ISSUE 13); "
        "includes the ISSUE 17 legs — fused page-walk kernel "
        "token-exact at full precision, int4 page_bytes exactly half "
        "of int8's",
    )
    parser.add_argument(
        "--tp", type=int, default=0,
        help="also run the sharded-serving arm at this TP width: the "
        "base stream through a TensorParallel engine on a {'model': N} "
        "mesh, token-identical to replicated, same fetch budget, KV "
        "really sharded, and a clean decode-HLO collective audit "
        "(ISSUE 15)",
    )
    parser.add_argument(
        "--sentry", action="store_true",
        help="also run the contract-sentry arm: a sentry-instrumented "
        "engine over the base stream (zero steady recompiles, fetch "
        "accounting equal to an independent monkeypatch spy, zero "
        "re-uploads), then one injected violation per probe class — "
        "each must yield exactly one typed flight event and one "
        "auto-dump naming its trigger (ISSUE 19)",
    )
    parser.add_argument(
        "--slo", action="store_true",
        help="also run the SLO-tier arm: a priority_classes=2 engine "
        "preempting a low-class slot (KV swap to host) for a class-0 "
        "arrival, both streams token-exact to generate(), budget = "
        "chains + prefills + splices + counted swaps pinned by the spy "
        "AND the contract sentry, plus the chaos forced-preempt and "
        "single-class-equals-FIFO legs (ISSUE 20)",
    )
    args = parser.parse_args(argv)
    if not args.selftest:
        parser.print_help()
        return 2
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""
        ):
            # ad-hoc CPU runs match the tier-1 forced 8-device mesh
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip()
    from pytorch_distributed_training_tutorials_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    receipt = selftest(args.json, spec_k=args.spec_k,
                       adapters=args.adapters, chaos=args.chaos,
                       flight=args.flight, pipeline=args.pipeline,
                       router=args.router, paged=args.paged,
                       tp=args.tp, sentry=args.sentry, slo=args.slo)
    print(json.dumps(receipt))
    return 0 if receipt["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

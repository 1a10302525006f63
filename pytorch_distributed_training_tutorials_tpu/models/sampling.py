"""The serving sampling pipeline — ONE shared copy.

Temperature scaling, top-k, and nucleus (top-p) filtering used to live
inside :mod:`.generate`; the continuous-batching engine (:mod:`..serve`)
needs the identical pipeline per cache slot, and two drifting copies of
sampling semantics is how serving stacks grow subtle A/B bugs. This module
is the single implementation both consume:

- :func:`filter_logits` — the XLA-friendly top-k / nucleus filters
  (``lax.top_k`` with k << V, never a full-vocabulary sort);
- :func:`sample_logits` — one sampling decision for a whole batch sharing
  ONE PRNG key (the :func:`..models.generate.generate` contract);
- :func:`sample_logits_per_slot` — the same decision vmapped over per-slot
  keys, so each serving request's draw stream depends only on its own seed
  and emitted-token count, never on which other requests happen to share
  the decode batch;
- :func:`ngram_draft` / :func:`speculative_accept` — the speculative
  pipeline (prompt-lookup drafting, Saxena 2023; Leviathan et al. 2023
  verify) shared by the serving engine's speculate-k chain and
  ``generate(..., speculative_k=...)``: fixed shapes throughout, the
  accepted length is DATA, never a Python branch.

Greedy (``temperature == 0``) is argmax with an EXPLICIT lowest-index
tie-break (:func:`greedy_token`) and ignores filters and keys in all
variants — the path the token-exactness guarantees ride on. int8 serving
produces real logit ties (CLAUDE.md's kv_cache_dtype caveat); making the
tie-break explicit pins every greedy consumer — one-shot, per-slot, and
speculative verify — to the same winner by construction instead of by
backend argmax convention.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Candidate budget for nucleus (top_p) filtering when top_k is off. The
# nucleus cutoff only depends on the highest-probability tokens, so it is
# computed from ``lax.top_k(logits, cap)`` instead of a full-vocabulary
# descending sort — at a 32-50k vocab the O(V log V) sort inside the
# per-token decode scan rivals the lm_head matmul itself. Exact whenever
# the nucleus holds <= cap tokens (always, for practical p and peaked LM
# distributions); a flatter-than-cap distribution degrades gracefully to
# an implicit additional top-1024 cut.
_NUCLEUS_CANDIDATES = 1024


def greedy_token(logits):
    """Greedy next token over ``(..., V)`` logits with a DETERMINISTIC
    lowest-index tie-break, spelled out instead of inherited from the
    backend's argmax convention: among all positions holding the row
    maximum, the smallest vocabulary index wins. The greedy serving paths
    (one-shot, per-slot, speculative verify) must all resolve an exact tie
    to the SAME token or token-exactness guarantees silently become backend
    properties. Two reductions, a maximum and a minimum over the tied index
    set: under a vocabulary-sharded head each is one all-reduce (a single
    (value, index) reduction gathers its partial results instead, which the
    tensor-parallel decode's all-reduce-only audit refuses).

    Both reductions must read the SAME logits. Under ``jit`` on the TPU they
    did not: the logits arrive as bfloat16 widened to float32, the compiler
    may keep the unrounded float32 in one consumer's fusion and the rounded
    value in the other's (excess precision), no element then equalled the
    maximum and the sentinel ``vocab_size`` was served (PERF.md, PR 26).
    The barrier makes the logits one materialized array that both read."""
    logits = jax.lax.optimization_barrier(logits)
    v = logits.shape[-1]
    top = jnp.max(logits, axis=-1, keepdims=True)
    tied = jnp.where(logits == top, jnp.arange(v), v)
    return jnp.min(tied, axis=-1).astype(jnp.int32)


def filter_logits(logits, top_k: int, top_p: float):
    """Standard serving logit filters, XLA-friendly (static shapes, no
    data-dependent control flow, no full-vocab sort — ``lax.top_k`` with
    k << V is the TPU idiom): ``top_k`` keeps the k highest logits,
    ``top_p`` (nucleus) keeps the smallest set of tokens whose softmax
    mass reaches p. Disallowed tokens get -inf so ``categorical`` never
    picks them. Both filters compose (k first, then p, the usual order);
    when both are active one ``lax.top_k`` call feeds both, and the
    nucleus mass is normalized over the k-filtered support (exactly what
    softmax-after-the-k-filter yields)."""
    v = logits.shape[-1]
    k_active = 0 < top_k < v
    vals = None
    if k_active:
        vals = jax.lax.top_k(logits, top_k)[0]  # descending
        kth = vals[..., -1:]
        # strict < keeps boundary ties, same as argmax keeping the first
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        if vals is None:
            vals = jax.lax.top_k(logits, min(v, _NUCLEUS_CANDIDATES))[0]
        # softmax mass of each candidate under the (k-)filtered
        # distribution; one O(V) logsumexp pass, no sort
        z = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        probs = jnp.exp(vals - z)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens while the mass BEFORE them is < p (the first token
        # is always kept, matching the conventional implementation); if
        # every candidate is kept the cutoff is the last candidate value,
        # so tokens below the candidate set are dropped — the documented
        # implicit top-cap degradation
        keep = (cum - probs) < top_p
        cutoff = jnp.min(
            jnp.where(keep, vals, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


@jax.named_scope("sampling")
def sample_logits(
    logits, key, temperature: float, top_k: int = 0, top_p: float = 1.0
):
    """One next-token decision over ``(..., V)`` float32 logits.

    Greedy argmax when ``temperature == 0`` (key untouched); otherwise
    temperature BEFORE the filters (the standard pipeline order — top_k is
    order-invariant but the nucleus is not: it must be taken over the
    temperature-sharpened distribution), then one ``categorical`` draw for
    the whole batch from a single split of ``key``. Returns ``(tokens
    int32, carried key)``."""
    if temperature > 0:
        key, sub = jax.random.split(key)
        logits = filter_logits(logits / temperature, top_k, top_p)
        nxt = jax.random.categorical(sub, logits, axis=-1)
    else:
        nxt = greedy_token(logits)
    return nxt.astype(jnp.int32), key


@jax.named_scope("sampling")
def sample_logits_per_slot(
    logits, keys, temperature: float, top_k: int = 0, top_p: float = 1.0
):
    """:func:`sample_logits` with per-row PRNG streams: ``logits`` is
    ``(S, V)``, ``keys`` is ``(S, 2)`` uint32 — slot s draws from its own
    key, split exactly like the shared-key variant (carry = row 0 of the
    split, draw = row 1), so a request's sampled tokens are a function of
    its seed and its position in its own stream only. Co-scheduling,
    slot assignment, and chain boundaries cannot change them. Returns
    ``(tokens (S,) int32, carried keys (S, 2))``."""
    if temperature > 0:
        split = jax.vmap(jax.random.split)(keys)  # (S, 2, 2)
        keys, subs = split[:, 0], split[:, 1]
        filt = filter_logits(logits / temperature, top_k, top_p)
        nxt = jax.vmap(jax.random.categorical)(subs, filt)
    else:
        nxt = greedy_token(logits)
    return nxt.astype(jnp.int32), keys


# ---------------------------------------------------------------------------
# speculative decoding: prompt-lookup draft + vectorized accept/reject
# ---------------------------------------------------------------------------

def ngram_draft(hist, hist_len, k: int, ngram: int):
    """Draft ``k`` tokens per row from the row's OWN recent-token history
    — prompt-lookup decoding (Saxena 2023): no second model, the draft
    "table" is the longest suffix match inside the tokens already known.

    ``hist``: ``(B, W)`` int32 token history per row (prompt + everything
    emitted so far, junk beyond ``hist_len``); ``hist_len``: ``(B,)``
    int32 count of valid tokens (the token at ``hist_len - 1`` is the
    next decode input). All shapes are static and every step is a
    gather/compare — no host round-trip, no data-dependent control flow,
    so this runs inside the serving engine's compiled decode chain.

    For each row: score every candidate end position ``i < hist_len - 1``
    by how many of the current trailing ``ngram`` tokens it matches
    (compare + cumprod = longest-suffix length), pick the longest match
    (ties -> the most recent occurrence, encoded in one score), and copy
    the ``k`` tokens FOLLOWING it as the draft. No match, or a match too
    close to the end to have ``k`` continuations: the missing positions
    fill with the row's last token — a draft is only a guess for the
    verify forward to judge, so a bad one costs nothing extra
    (:func:`speculative_accept` simply rejects it).
    """
    if k < 1:
        raise ValueError("k must be >= 1")
    if ngram < 1:
        raise ValueError("ngram must be >= 1")
    b, w = hist.shape
    rows = jnp.arange(b)
    back = jnp.arange(ngram)  # t: tokens back from the end of history
    # suffix[r, t] = hist[r, L-1-t] — the trailing ngram, newest first
    suf_idx = hist_len[:, None] - 1 - back[None, :]
    suf = hist[rows[:, None], jnp.maximum(suf_idx, 0)]
    # cand[r, i, t] = hist[r, i-t] — the ngram ENDING at candidate i
    idx = jnp.arange(w)[None, :, None] - back[None, None, :]
    cand = hist[rows[:, None, None], jnp.maximum(idx, 0)]
    eq = (
        (cand == suf[:, None, :])
        & (idx >= 0)
        & (suf_idx[:, None, :] >= 0)
    )
    # longest-suffix match length at each candidate: leading run of the
    # newest-first comparison (cumprod), summed
    mlen = jnp.cumprod(eq.astype(jnp.int32), axis=-1).sum(-1)  # (B, W)
    pos = jnp.arange(w)[None, :]
    # a real PRIOR occurrence: matches >= 1 token and ends early enough
    # to have at least one continuation (also excludes the trivial
    # self-match at L-1)
    valid = (mlen >= 1) & (pos < hist_len[:, None] - 1)
    score = jnp.where(valid, mlen * w + pos, -1)
    best = jnp.argmax(score, axis=-1)  # scores are distinct per position
    has = jnp.max(score, axis=-1) >= 0
    cont = best[:, None] + 1 + jnp.arange(k)[None, :]
    in_range = cont <= hist_len[:, None] - 1
    last = hist[rows, jnp.maximum(hist_len - 1, 0)]
    draft = jnp.where(
        has[:, None] & in_range,
        hist[rows[:, None], jnp.minimum(cont, w - 1)],
        last[:, None],
    )
    return draft.astype(jnp.int32)


@jax.named_scope("sampling")
def speculative_accept(
    logits, draft, keys, temperature: float, top_k: int = 0,
    top_p: float = 1.0,
):
    """Vectorized accept/reject for a deterministic (point-mass) draft —
    the verify half of speculative decoding (Leviathan et al. 2023),
    fixed shapes only: the accepted length comes out as DATA, never as a
    Python branch.

    ``logits``: ``(B, k+1, V)`` float32 verify logits — position ``i``
    is the model's distribution for the token FOLLOWING input ``i`` of
    the ``[last_tok, draft_0..draft_{k-1}]`` chunk. ``draft``: ``(B, k)``
    int32. ``keys``: ``(B, 2)`` uint32 per-row PRNG streams (untouched
    when greedy). Returns ``(emitted (B, k+1) int32, n_accept (B,)
    int32, keys)``: ``emitted[:, :n_accept]`` are the accepted draft
    tokens, ``emitted[:, n_accept]`` is the bonus token from the
    verifier's own distribution, columns past ``n_accept`` are padding
    the caller must ignore — so every call emits ``n_accept + 1``
    tokens, between 1 and k+1.

    Greedy: accept while ``draft[i] == greedy_token(logits[i])``
    (cumprod prefix mask); the emitted block IS the greedy rollout, so
    speculation is exact by construction. ``temperature > 0``: the
    standard rejection rule specialized to a point-mass proposal
    ``q = delta(draft_i)`` — accept draft ``i`` with probability
    ``p_i(draft_i)`` (that is ``min(1, p/q)`` at ``q = 1``); on the
    first rejection sample the bonus from the residual
    ``norm(max(p - q, 0))``, which is ``p`` with the rejected draft
    token masked out; all k accepted -> bonus from ``p_k`` untouched.
    The output distribution equals non-speculative sampling exactly;
    the DRAW STREAM differs (3 splits per verify vs 1 per token), so
    sampled sequences are distributionally — not bitwise — equivalent.
    """
    b, k1, v = logits.shape
    k = k1 - 1
    rows = jnp.arange(b)
    if temperature > 0:
        logp = jax.nn.log_softmax(
            filter_logits(logits / temperature, top_k, top_p), axis=-1
        )
        split = jax.vmap(lambda kk: jax.random.split(kk, 3))(keys)
        keys, ukeys, ckeys = split[:, 0], split[:, 1], split[:, 2]
        u = jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(ukeys)
        p_draft = jnp.exp(
            jnp.take_along_axis(logp[:, :k], draft[..., None], axis=-1)
        )[..., 0]
        ok = u < p_draft
    else:
        out = greedy_token(logits)  # (B, k+1)
        ok = draft == out[:, :k]
    acc = jnp.cumprod(ok.astype(jnp.int32), axis=-1)
    n_accept = acc.sum(-1)  # longest accepted prefix, as data
    if temperature > 0:
        bonus_logits = logp[rows, n_accept]  # (B, V)
        d_rej = draft[rows, jnp.minimum(n_accept, k - 1)]
        rejected = (n_accept < k)[:, None]
        residual = jnp.where(
            rejected & (jnp.arange(v)[None, :] == d_rej[:, None]),
            -jnp.inf, bonus_logits,
        )
        bonus = jax.vmap(jax.random.categorical)(ckeys, residual)
        emitted = jnp.where(
            jnp.arange(k1)[None, :] < n_accept[:, None],
            jnp.concatenate([draft, draft[:, -1:]], axis=1),
            bonus[:, None].astype(jnp.int32),
        )
    else:
        emitted = out  # accepted prefix == draft there, bonus at n_accept
    return (
        emitted.astype(jnp.int32),
        n_accept.astype(jnp.int32),
        keys,
    )

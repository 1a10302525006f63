"""KV-cache generation: cache-exactness vs full re-forward, sampling, LM demo.

The reference loads Llama and imports GenerationConfig without ever
generating (SURVEY.md 5.7); these tests pin this framework's decode path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tutorials_tpu.models import (
    TransformerConfig,
    TransformerLM,
)
from pytorch_distributed_training_tutorials_tpu.models.generate import generate


def _model(scan_layers=False, **kw):
    base = dict(
        vocab_size=32, d_model=32, n_layers=2, n_heads=4, max_seq_len=32,
        scan_layers=scan_layers,
    )
    base.update(kw)
    cfg = TransformerConfig(**base)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, 4), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    return model, params


def _oracle_greedy(model, params, prompt, max_new):
    """Re-forward the full prefix each step (no cache) — the ground truth."""
    tokens = jnp.asarray(prompt, jnp.int32)
    for _ in range(max_new):
        logits = model.apply({"params": params}, tokens)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        tokens = jnp.concatenate(
            [tokens, nxt[:, None].astype(jnp.int32)], axis=1
        )
    return tokens


@pytest.mark.parametrize("scan_layers", [False, True])
def test_cached_decode_matches_full_reforward(scan_layers):
    """Greedy generation through the KV cache must equal argmax decoding by
    re-running the full prefix — the cache is an optimization, not a model."""
    model, params = _model(scan_layers=scan_layers)
    rng = np.random.Generator(np.random.PCG64(0))
    prompt = jnp.asarray(rng.integers(0, 32, (2, 5)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=8)
    ref = _oracle_greedy(model, params, prompt, 8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # prompt is preserved verbatim
    np.testing.assert_array_equal(np.asarray(out[:, :5]), np.asarray(prompt))


def test_single_decode_step_logits_match_full_forward():
    """One cached decode step at position t reproduces the full forward's
    logits at position t (float tolerance)."""
    model, params = _model()
    rng = np.random.Generator(np.random.PCG64(1))
    tokens = jnp.asarray(rng.integers(0, 32, (1, 6)), jnp.int32)

    full = model.apply({"params": params}, tokens)  # (1, 6, vocab)
    cache = jax.tree_util.tree_map(
        jnp.zeros_like,
        model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32), decode=True
        )["cache"],
    )
    step_logits = []
    for t in range(6):
        lg, upd = model.apply(
            {"params": params, "cache": cache},
            tokens[:, t : t + 1],
            decode=True,
            mutable=["cache"],
        )
        cache = upd["cache"]
        step_logits.append(lg[:, 0])
    np.testing.assert_allclose(
        np.asarray(jnp.stack(step_logits, axis=1)),
        np.asarray(full),
        rtol=2e-4,
        atol=2e-4,
    )


@pytest.mark.parametrize("scan_layers", [False, True])
def test_batched_prefill_matches_stepwise_cache(scan_layers):
    """One prefill=True forward must leave the cache exactly as P one-token
    decode steps would (same K/V contents, same cache_index) and emit the
    full forward's logits — the prefill is a batching of the decode path,
    not a different model."""
    model, params = _model(scan_layers=scan_layers)
    rng = np.random.Generator(np.random.PCG64(3))
    tokens = jnp.asarray(rng.integers(0, 32, (2, 6)), jnp.int32)

    pre_logits, pre = model.apply(
        {"params": params}, tokens, prefill=True, mutable=["cache"]
    )
    cache = jax.tree_util.tree_map(
        jnp.zeros_like,
        model.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32), decode=True
        )["cache"],
    )
    for t in range(6):
        step_logits, upd = model.apply(
            {"params": params, "cache": cache},
            tokens[:, t : t + 1],
            decode=True,
            mutable=["cache"],
        )
        cache = upd["cache"]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
        ),
        pre["cache"],
        cache,
    )
    # prefill emits the LAST position's logits only (the next-token feed);
    # they must equal the full training forward's final position
    assert pre_logits.shape == (2, 1, 32)
    full = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(
        np.asarray(pre_logits[:, 0]), np.asarray(full[:, -1]),
        rtol=1e-6, atol=1e-6,
    )
    # ... and the stepwise decode path's logits at the same position
    np.testing.assert_allclose(
        np.asarray(pre_logits[:, -1]), np.asarray(step_logits[:, 0]),
        rtol=2e-4, atol=2e-4,
    )


def test_sampling_is_seeded_and_in_vocab():
    model, params = _model()
    prompt = jnp.zeros((2, 3), jnp.int32)
    a = generate(model, params, prompt, 6, temperature=1.0,
                 rng=jax.random.PRNGKey(7))
    b = generate(model, params, prompt, 6, temperature=1.0,
                 rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # seeded
    assert int(jnp.max(a)) < 32 and int(jnp.min(a)) >= 0


def test_generate_validates_lengths_and_rng():
    model, params = _model()
    prompt = jnp.zeros((1, 30), jnp.int32)
    with pytest.raises(ValueError, match="exceeds"):
        generate(model, params, prompt, 10)
    with pytest.raises(ValueError, match="requires rng"):
        generate(model, params, prompt, 1, temperature=0.5, rng=None)


def test_generate_rejects_empty_prompt():
    model, params = _model()
    with pytest.raises(ValueError, match="at least one token"):
        generate(model, params, jnp.zeros((1, 0), jnp.int32), 4)


def test_repeated_calls_reuse_compiled_program():
    from pytorch_distributed_training_tutorials_tpu.models.generate import (
        _compiled_generate,
    )

    model, params = _model()
    prompt = jnp.zeros((1, 3), jnp.int32)
    _compiled_generate.cache_clear()
    generate(model, params, prompt, 4)
    generate(model, params, prompt, 4)
    info = _compiled_generate.cache_info()
    assert info.misses == 1 and info.hits == 1


def test_generate_with_ring_attention_any_prompt_length():
    """An SP-configured model (ring attention_fn) must generate for ANY
    prompt length: prefill falls back to the dense causal path (equivalent
    math), so the seq-axis divisibility constraint of the ring schedule
    does not apply to prompts (ADVICE r3)."""
    from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
    from pytorch_distributed_training_tutorials_tpu.parallel.ring_attention import (
        make_ring_attention,
    )

    mesh = create_mesh({"seq": 4})
    model, params = _model(attention_fn=make_ring_attention(mesh))
    dense_model, _ = _model()
    rng = np.random.Generator(np.random.PCG64(3))
    # 5 does not divide the 4-wide seq axis — pre-fix this failed in the
    # shard_map sharding check
    prompt = jnp.asarray(rng.integers(0, 32, (2, 5)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=4)
    ref = _oracle_greedy(dense_model, params, prompt, 4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_request_sized_cache_window_matches_full():
    """generate() rebuilds the module with a request-sized KV cache when
    total << max_seq_len (_window_model); the windowed serve must be
    token-identical to the full-cache model and preserve non-cfg module
    fields (dataclasses.replace on the module, not type(model)(cfg))."""
    model, params = _model(max_seq_len=256)
    rng = np.random.Generator(np.random.PCG64(11))
    prompt = jnp.asarray(rng.integers(0, 32, (2, 6)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=6)
    ref = _oracle_greedy(model, params, prompt, 6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # the rebuild branch actually ran (window 16 < 256)
    from pytorch_distributed_training_tutorials_tpu.models.generate import _window_model
    assert _window_model(model, 12).cfg.max_seq_len == 16


def test_filter_logits_top_k_and_top_p():
    """_filter_logits: top_k keeps exactly the k highest logits; top_p
    keeps the smallest prefix of the sorted distribution reaching mass p
    (first token always kept); disallowed entries become -inf."""
    from pytorch_distributed_training_tutorials_tpu.models.generate import _filter_logits

    logits = jnp.asarray([[2.0, 0.0, 1.0, -1.0]])
    k2 = np.asarray(_filter_logits(logits, top_k=2, top_p=1.0))
    np.testing.assert_array_equal(
        np.isfinite(k2[0]), [True, False, True, False]
    )
    # top_p tiny -> only the argmax survives
    p_small = np.asarray(_filter_logits(logits, top_k=0, top_p=1e-6))
    np.testing.assert_array_equal(
        np.isfinite(p_small[0]), [True, False, False, False]
    )
    # top_p=1.0 and top_k=0 are no-ops
    np.testing.assert_array_equal(
        np.asarray(_filter_logits(logits, top_k=0, top_p=1.0)),
        np.asarray(logits),
    )
    # per-row independence: each row filters against its own top-k
    two = jnp.asarray([[2.0, 0.0, 1.0, -1.0], [-1.0, 5.0, 4.0, 0.0]])
    k1 = np.asarray(_filter_logits(two, top_k=1, top_p=1.0))
    np.testing.assert_array_equal(
        np.isfinite(k1), [[True, False, False, False],
                          [False, True, False, False]]
    )


def test_generate_sampling_filters():
    """The serving sampling surface: top_k=1 reduces sampling to greedy;
    top_k=0/top_p=1.0 with the same rng reproduce unfiltered sampling; a
    tiny nucleus also reduces to greedy."""
    model, params = _model()
    rng_np = np.random.Generator(np.random.PCG64(5))
    prompt = jnp.asarray(rng_np.integers(0, 32, (2, 4)), jnp.int32)
    key = jax.random.PRNGKey(42)

    greedy = generate(model, params, prompt, max_new_tokens=6)
    k1 = generate(model, params, prompt, max_new_tokens=6,
                  temperature=0.8, top_k=1, rng=key)
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(greedy))

    plain = generate(model, params, prompt, max_new_tokens=6,
                     temperature=0.8, rng=key)
    off = generate(model, params, prompt, max_new_tokens=6,
                   temperature=0.8, top_k=0, top_p=1.0, rng=key)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(plain))

    p_tiny = generate(model, params, prompt, max_new_tokens=6,
                      temperature=0.8, top_p=1e-6, rng=key)
    np.testing.assert_array_equal(np.asarray(p_tiny), np.asarray(greedy))

    with pytest.raises(ValueError, match="top_p"):
        generate(model, params, prompt, 2, temperature=0.5, top_p=0.0,
                 rng=key)
    with pytest.raises(ValueError, match="top_k"):
        generate(model, params, prompt, 2, temperature=0.5, top_k=-1,
                 rng=key)


def test_greedy_ignores_filter_args_in_compile_cache():
    """Greedy calls normalize top_k/top_p out of the compile key: cosmetic
    filter args on a temperature=0 call must not retrace (compile is the
    multi-second cost at serving scale)."""
    from pytorch_distributed_training_tutorials_tpu.models.generate import (
        _compiled_generate,
    )

    model, params = _model()
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    out_a = generate(model, params, prompt, max_new_tokens=4)
    size_after_first = _compiled_generate.cache_info().currsize
    out_b = generate(model, params, prompt, max_new_tokens=4, top_k=50,
                     top_p=0.9)
    assert _compiled_generate.cache_info().currsize == size_after_first
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


def _sorted_reference_filter(logits, top_k, top_p):
    """The textbook sorted implementation (what _filter_logits computed
    before the lax.top_k rewrite) — the parity oracle for the sort-free
    version."""
    logits = np.asarray(logits, np.float32).copy()
    if 0 < top_k < logits.shape[-1]:
        kth = np.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = np.where(logits < kth, -np.inf, logits)
    if top_p < 1.0:
        s = -np.sort(-logits, axis=-1)
        e = np.exp(s - s[..., :1])
        probs = e / e.sum(axis=-1, keepdims=True)
        cum = np.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p
        cutoff = np.min(np.where(keep, s, np.inf), axis=-1, keepdims=True)
        logits = np.where(logits < cutoff, -np.inf, logits)
    return logits


@pytest.mark.parametrize("top_k,top_p", [
    (0, 0.9), (0, 0.3), (5, 1.0), (5, 0.7), (17, 0.95), (0, 0.999),
])
def test_filter_logits_matches_sorted_reference(top_k, top_p):
    """The lax.top_k-based filters are draw-for-draw identical to the
    full-sort textbook implementation whenever the nucleus fits in the
    candidate budget (always at this vocab: V=97 < _NUCLEUS_CANDIDATES)."""
    from pytorch_distributed_training_tutorials_tpu.models.generate import _filter_logits

    rng = np.random.Generator(np.random.PCG64(3))
    logits = jnp.asarray(rng.normal(size=(4, 97)) * 3.0, jnp.float32)
    got = np.asarray(_filter_logits(logits, top_k=top_k, top_p=top_p))
    want = _sorted_reference_filter(logits, top_k, top_p)
    # identical support and identical surviving values
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(want))
    np.testing.assert_allclose(
        got[np.isfinite(got)], want[np.isfinite(want)], rtol=1e-6
    )


def test_filter_logits_compiles_without_full_vocab_sort():
    """Round-4 review item: at a real vocab the per-step O(V log V) sorts
    rivaled the lm_head matmul. The filters must lower through lax.top_k
    (a partial top-k selection), never the sort primitive — asserted on
    the jaxpr, which is backend-independent (on CPU the TopK custom call
    may itself expand to a sort during XLA lowering; the contract here is
    that *we* never request a full-vocabulary sort)."""
    from pytorch_distributed_training_tutorials_tpu.models.generate import _filter_logits

    logits = jnp.zeros((2, 32768), jnp.float32)
    for kw in (dict(top_k=50, top_p=0.9), dict(top_k=0, top_p=0.9),
               dict(top_k=50, top_p=1.0)):
        jaxpr = jax.make_jaxpr(
            lambda x, kw=kw: _filter_logits(x, **kw)
        )(logits)
        prims = {eqn.primitive.name for eqn in jaxpr.jaxpr.eqns}
        assert "sort" not in prims, (kw, prims)
        assert any("top_k" in p for p in prims), (kw, prims)


def test_filter_logits_nucleus_cap_degrades_to_top_cap():
    """When the nucleus needs more than _NUCLEUS_CANDIDATES tokens (flat
    distribution over a big vocab), the filter degrades to an implicit
    top-cap cut: exactly the cap's worth of (highest) tokens survive, and
    their values are untouched — the documented approximation, pinned."""
    import importlib

    G = importlib.import_module(
        "pytorch_distributed_training_tutorials_tpu.models.generate"
    )

    v = 4 * G._NUCLEUS_CANDIDATES
    rng = np.random.Generator(np.random.PCG64(9))
    # near-uniform: nucleus at p=0.99 would need ~0.99*V >> cap tokens
    logits = jnp.asarray(rng.normal(size=(1, v)) * 1e-3, jnp.float32)
    out = np.asarray(G._filter_logits(logits, top_k=0, top_p=0.99))
    kept = np.isfinite(out[0])
    assert kept.sum() == G._NUCLEUS_CANDIDATES
    # the survivors are the top-cap tokens, values preserved
    order = np.argsort(-np.asarray(logits[0]))
    np.testing.assert_array_equal(np.sort(np.nonzero(kept)[0]),
                                  np.sort(order[:G._NUCLEUS_CANDIDATES]))
    np.testing.assert_array_equal(out[0][kept], np.asarray(logits)[0][kept])


# ------------------------------------------------- speculative decoding

def test_greedy_tie_break_is_lowest_index():
    """Exact logit ties resolve to the smallest vocabulary index in every
    greedy consumer — the explicit contract the int8 near-tie paths and
    the speculative verify both lean on (a tie resolved differently in
    the verify forward vs the sequential path would silently break the
    speculation-is-invisible guarantee)."""
    from pytorch_distributed_training_tutorials_tpu.models.sampling import (
        greedy_token,
        sample_logits,
        sample_logits_per_slot,
    )

    logits = jnp.asarray(
        [[0.0, 3.0, 3.0, 1.0], [2.0, 2.0, 2.0, 2.0]], jnp.float32
    )
    np.testing.assert_array_equal(np.asarray(greedy_token(logits)), [1, 0])
    tok, _ = sample_logits(logits, jax.random.PRNGKey(0), 0.0)
    np.testing.assert_array_equal(np.asarray(tok), [1, 0])
    tok, _ = sample_logits_per_slot(
        logits, jnp.zeros((2, 2), jnp.uint32), 0.0
    )
    np.testing.assert_array_equal(np.asarray(tok), [1, 0])


def test_ngram_draft_copies_the_continuation_of_the_longest_match():
    """A history whose trailing n-gram occurred before drafts the tokens
    that followed that occurrence; rows without any prior match fall back
    to repeating their last token (a harmless guess for the verifier)."""
    from pytorch_distributed_training_tutorials_tpu.models.sampling import ngram_draft

    hist = jnp.asarray(
        [
            # ...5 6 7 [8 9] then later [8 9] again -> draft 5 6 7
            [8, 9, 5, 6, 7, 8, 9, 0, 0, 0],
            # no repeat anywhere -> fall back to last token (4)
            [1, 2, 3, 4, 0, 0, 0, 0, 0, 0],
        ],
        jnp.int32,
    )
    hist_len = jnp.asarray([7, 4], jnp.int32)
    draft = np.asarray(ngram_draft(hist, hist_len, k=3, ngram=2))
    np.testing.assert_array_equal(draft[0], [5, 6, 7])
    np.testing.assert_array_equal(draft[1], [4, 4, 4])


def test_ngram_draft_prefers_longest_then_most_recent_match():
    """Scoring is (match length, recency): a longer suffix match beats a
    more recent shorter one, and among equal lengths the most recent
    occurrence wins."""
    from pytorch_distributed_training_tutorials_tpu.models.sampling import ngram_draft

    # trailing bigram [2 3]: position 1 matches [2 3] (len 2, cont 7),
    # position 5 matches only [.. 3]? no — build it explicitly:
    # hist = 2 3 7 1 2 3 9 | current suffix [2 3] occurs at idx 1 (->7)
    # and idx 5 (->9); most recent (idx 5) must win
    hist = jnp.asarray([[2, 3, 7, 1, 2, 3, 9, 2, 3, 0]], jnp.int32)
    hist_len = jnp.asarray([9], jnp.int32)
    draft = np.asarray(ngram_draft(hist, hist_len, k=1, ngram=2))
    np.testing.assert_array_equal(draft[0], [9])


def test_speculative_accept_greedy_prefix_and_bonus():
    """Greedy accept: the emitted block's first n_accept tokens equal the
    draft where it matches the verifier's greedy rollout, and position
    n_accept is the verifier's own token — so emitted[:n_accept + 1] IS
    the greedy continuation regardless of draft quality."""
    from pytorch_distributed_training_tutorials_tpu.models.sampling import (
        speculative_accept,
    )

    v = 8
    # verifier greedy tokens per position: [3, 5, 1]
    logits = jnp.full((1, 3, v), -10.0).at[0, 0, 3].set(0.0)
    logits = logits.at[0, 1, 5].set(0.0).at[0, 2, 1].set(0.0)
    keys = jnp.zeros((1, 2), jnp.uint32)
    # draft [3, 5] fully accepted -> emits [3, 5, 1] (bonus from p_k)
    emitted, n_acc, _ = speculative_accept(
        logits, jnp.asarray([[3, 5]], jnp.int32), keys, 0.0
    )
    assert int(n_acc[0]) == 2
    np.testing.assert_array_equal(np.asarray(emitted[0]), [3, 5, 1])
    # draft [3, 4] rejected at position 1 -> emits [3, 5, ...] (2 tokens)
    emitted, n_acc, _ = speculative_accept(
        logits, jnp.asarray([[3, 4]], jnp.int32), keys, 0.0
    )
    assert int(n_acc[0]) == 1
    np.testing.assert_array_equal(np.asarray(emitted[0, :2]), [3, 5])
    # draft [0, 5]: first token wrong -> only the bonus token emits
    emitted, n_acc, _ = speculative_accept(
        logits, jnp.asarray([[0, 5]], jnp.int32), keys, 0.0
    )
    assert int(n_acc[0]) == 0
    assert int(emitted[0, 0]) == 3


def test_speculative_accept_sampled_point_mass_limits():
    """The rejection rule at its deterministic limits: a draft token
    carrying ~all probability mass is always accepted; one carrying ~zero
    mass is always rejected and the bonus comes from the residual — which
    can never be the rejected token itself."""
    from pytorch_distributed_training_tutorials_tpu.models.sampling import (
        speculative_accept,
    )

    v, k = 8, 2
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(64))
    sure = jnp.full((1, k + 1, v), -30.0)
    sure = sure.at[0, 0, 3].set(0.0).at[0, 1, 5].set(0.0)
    sure = sure.at[0, 2, 1].set(0.0)
    for i in range(0, 64, 2):
        e, n, _ = speculative_accept(
            sure, jnp.asarray([[3, 5]], jnp.int32), keys[i:i + 1], 1.0
        )
        assert int(n[0]) == 2
        np.testing.assert_array_equal(np.asarray(e[0]), [3, 5, 1])
    for i in range(0, 64, 2):
        e, n, _ = speculative_accept(
            sure, jnp.asarray([[0, 5]], jnp.int32), keys[i:i + 1], 1.0
        )
        assert int(n[0]) == 0  # p(0) ~ 0 -> reject
        assert int(e[0, 0]) != 0  # residual masks the rejected token


@pytest.mark.parametrize("scan_layers", [False, True])
@pytest.mark.parametrize("k", [1, 3])
def test_speculative_generate_greedy_token_identical(scan_layers, k):
    """generate(speculative_k=k) greedy output is token-identical to
    plain generate() — accepted drafts are verified equal to the greedy
    rollout and the bonus IS the greedy token at the rejection point, so
    speculation only changes the step count, never the tokens. Pinned
    across the unrolled and nn.scan layouts and batch > 1 (per-row
    accepted lengths diverge -> the widened per-row cache counters)."""
    model, params = _model(scan_layers=scan_layers)
    rng = np.random.Generator(np.random.PCG64(5))
    # a repetitive prompt so drafting actually fires, plus a random row
    rep = np.tile([3, 4, 5], 3)[:8]
    rand = rng.integers(0, 32, (8,))
    prompt = jnp.asarray(np.stack([rep, rand]), jnp.int32)
    base = generate(model, params, prompt, max_new_tokens=14)
    spec = generate(
        model, params, prompt, max_new_tokens=14, speculative_k=k
    )
    np.testing.assert_array_equal(np.asarray(spec), np.asarray(base))


def test_speculative_generate_max_new_one_and_validation():
    model, params = _model()
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    base = generate(model, params, prompt, max_new_tokens=1)
    spec = generate(
        model, params, prompt, max_new_tokens=1, speculative_k=2
    )
    np.testing.assert_array_equal(np.asarray(spec), np.asarray(base))
    with pytest.raises(ValueError):
        generate(model, params, prompt, 4, speculative_k=-1)
    with pytest.raises(ValueError):
        generate(model, params, prompt, 4, speculative_k=2, spec_ngram=0)


def test_speculative_generate_sampled_runs_and_is_seeded():
    """Sampled speculative generation: in-vocab, reproducible per rng,
    and a different rng changes the stream (distributional exactness is
    pinned at the unit level — the draw stream legitimately differs from
    non-speculative sampling)."""
    model, params = _model()
    prompt = jnp.asarray([[3, 4, 5, 3, 4, 5, 3, 4]], jnp.int32)
    kw = dict(max_new_tokens=12, temperature=0.9, speculative_k=2)
    a = generate(model, params, prompt, rng=jax.random.PRNGKey(7), **kw)
    b = generate(model, params, prompt, rng=jax.random.PRNGKey(7), **kw)
    c = generate(model, params, prompt, rng=jax.random.PRNGKey(8), **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert ((np.asarray(a) >= 0) & (np.asarray(a) < 32)).all()

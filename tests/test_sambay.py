"""Layers with recurrent state, window rings and one shared cache
(``models/sambay.py``; ISSUE 34) against the plain reference
(``benchmark/blocks/sambay/reference.py``: float32, no cache), at toy widths
on the CPU with weights from a seed: each mixer alone, prefill and cached
decode against the reference's full forward pass (logits, not tokens), the
state of a padded bucket, the ring past a wrap, prefill's one served
position, ``ServeEngine`` against ``generate()``, and every refusal's words.

Tolerances, in units of the logits' deviation (0.23 at these widths with
the published ``initializer_range`` 0.02). Float32 through the cache against
the reference at ``HIGHEST``: both compute the same sums in float32 in
another order and read 2e-6 here: **2e-5**, which bfloat16 compute (1e-2 and
more) fails by two orders. int8 weights under W8A8: each activation row is
rounded to 8 bits a K tile, 1/254 of its largest value, through 8 layers:
0.03-0.06 here (it grows with the weights' deviation: 0.3 at 0.05), limit
**0.1**; the cached path against the program's own full pass is the same
arithmetic whatever the weights and holds 1e-4.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import harness, weights  # noqa: E402
from pytorch_distributed_training_tutorials_tpu.models import (  # noqa: E402
    TransformerConfig,
    TransformerLM,
    generate,
    quantize_lm_params,
    sambay,
)
from pytorch_distributed_training_tutorials_tpu.ops import (  # noqa: E402
    decode_attention as decode_attention_module,
)
from pytorch_distributed_training_tutorials_tpu.ops.selective_scan import (  # noqa: E402
    selective_scan,
    selective_scan_reference,
    selective_step,
)
from pytorch_distributed_training_tutorials_tpu.serve import (  # noqa: E402
    Request,
    ServeEngine,
)
from pytorch_distributed_training_tutorials_tpu.serve.slots import (  # noqa: E402
    slot_bytes,
)

BLOCK = harness.Block("sambay")
ref = BLOCK.reference
WINDOW = 128  # the serving window; the attention window (the ring) is 8
F32_TOL, INT8_TOL = 2e-5, 0.1  # of the logits' deviation


def toy_config(**over):
    cfg = harness.read_json(os.path.join(
        harness.BENCH, "configs", "phi-4-mini-flash-reasoning.json"))
    for k, v in cfg["rehearse"].items():
        cfg[k] = {**cfg[k], **v} if isinstance(v, dict) else v
    cfg["serve"] = dict(cfg["serve"], compute_dtype="float32",
                        kv_cache_dtype="float32", weights_dtype="float32")
    cfg["serve"].update(over)
    return cfg


@pytest.fixture(scope="module", params=["float32", "int8"])
def built(request):
    """(config, shape, reference tree, model, program tree) a weight type."""
    cfg = toy_config(weights_dtype=request.param)
    shape = ref.Shape.from_config(cfg)
    tree = weights.make(ref.leaf_shapes(shape), 7, request.param, 0.02)
    model = BLOCK.program.model(cfg, "serve", WINDOW)
    return cfg, shape, tree, model, BLOCK.program.to_program(tree, shape)


@pytest.fixture(scope="module")
def f32():
    cfg = toy_config()
    shape = ref.Shape.from_config(cfg)
    tree = weights.make(ref.leaf_shapes(shape), 7, "float32", 0.02)
    model = BLOCK.program.model(cfg, "serve", WINDOW)
    return cfg, shape, tree, model, BLOCK.program.to_program(tree, shape)


TOKENS = np.random.default_rng(3).integers(0, 512, 40)


def tolerance(cfg, logits) -> float:
    share = F32_TOL if cfg["serve"]["weights_dtype"] == "float32" else INT8_TOL
    return share * float(jnp.std(logits))


@functools.lru_cache(maxsize=None)
def _programs(model):
    """The model's prefill and decode step, jitted once a model (a flax
    module hashes by its fields)."""
    prefill = jax.jit(lambda p, t, last: model.apply(
        {"params": p}, t, prefill=True, mutable=["cache"], last_pos=last))
    step = jax.jit(lambda p, c, t: model.apply(
        {"params": p, "cache": c}, t, decode=True, mutable=["cache"]))
    full = jax.jit(lambda p, t: model.apply({"params": p}, t))
    return prefill, step, full


def full_logits(model, params, tokens):
    return _programs(model)[2](params, jnp.asarray(tokens[None]))[0]


def cached_logits(model, params, tokens, p_len, bucket):
    """Logits at positions ``p_len - 1 ..`` from a prefill of ``p_len``
    tokens right-padded to ``bucket``, then one decode step a token."""
    prefill, step, _ = _programs(model)
    pad = np.zeros((1, bucket), np.int32)
    pad[0, :p_len] = tokens[:p_len]
    lg, upd = prefill(params, jnp.asarray(pad), p_len - 1)
    out, cache = [lg[0, 0]], upd["cache"]
    for t in range(p_len, len(tokens)):
        lg, upd = step(params, cache, jnp.asarray(tokens[None, t:t + 1]))
        out.append(lg[0, 0])
        cache = upd["cache"]
    return jnp.stack(out), cache


# -- each mixer against the reference ---------------------------------------


def _lin(shape):
    from benchmark.blocks.gqa_swiglu.reference import linear

    return functools.partial(linear, precision="float32", weight_bits=8)


@pytest.mark.parametrize("kind", ["mamba", "window", "full", "gmu", "cross"])
def test_mixer_equals_the_reference(f32, kind):
    cfg, shape, tree, model, params = f32
    mcfg, lin = model.cfg, _lin(shape)
    drawn = ref.drawn(tree)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(24, shape.hidden_size)), jnp.float32)
    half = shape.half
    at = lambda g, i: jax.tree_util.tree_map(lambda t: t[i], g)  # noqa: E731
    if kind == "mamba":
        want, y_want = ref.mamba(x, drawn["mid_mamba"], shape, lin)
        got, y_got, _ = sambay.MambaMixer(mcfg).apply(
            {"params": params[f"block_{half}"]["mixer"]}, x[None])
        np.testing.assert_allclose(y_got[0], y_want, atol=1e-5, rtol=1e-5)
    elif kind in ("window", "full"):
        p, mine, l, win = (
            (at(drawn["layers_a"]["window"], 1),
             at(params["layers_a"]["window_block"]["attn"], 1), 3,
             shape.sliding_window)
            if kind == "window" else
            (drawn["mid_full"], params[f"block_{half + 1}"]["attn"], half + 1,
             None))
        want, _, _ = ref.attention(x, p, shape, lin, l, win)
        got, _, _ = sambay.DiffAttention(mcfg, kind).apply(
            {"params": mine}, x[None], None, l)
    elif kind == "gmu":
        g = at(drawn["layers_b"]["gmu"], 0)
        m = jnp.asarray(rng.normal(size=(24, shape.d_inner)), jnp.float32)
        want = lin(jax.nn.silu(lin(x, g["in_proj"])) * m, g["out_proj"])
        got, = sambay.GatedMemoryUnit(mcfg).apply(
            {"params": at(params["layers_b"]["gmu_block"]["mixer"], 0)},
            x[None], m[None])
    else:
        c = at(drawn["layers_b"]["cross"], 0)
        kv = jnp.asarray(rng.normal(
            size=(2, 24, shape.num_key_value_heads, shape.head_dim)), jnp.float32)
        want = ref.cross_attention(x, kv[0], kv[1], c, shape, lin, half + 3)
        pairs = lambda t: t.reshape(1, 24, -1, 2 * shape.head_dim)  # noqa: E731
        got, _, _ = sambay.DiffAttention(mcfg, "cross").apply(
            {"params": at(params["layers_b"]["cross_block"]["attn"], 0)},
            x[None], ("seq", pairs(kv[0]), pairs(kv[1])), half + 3)
    np.testing.assert_allclose(got[0], want, atol=1e-5, rtol=1e-5)


def test_layer_kinds_by_place():
    cfg = TransformerConfig(n_layers=32, mb_per_layer=2)
    kinds = [sambay.layer_kind(cfg, l) for l in range(32)]
    assert kinds[:16] == ["mamba", "window"] * 8
    assert kinds[16:18] == ["mamba", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    shape = ref.Shape.from_config(harness.read_json(os.path.join(
        harness.BENCH, "configs", "phi-4-mini-flash-reasoning.json")))
    assert kinds == [ref.kind_of(shape, l) for l in range(32)]


# -- the whole model through the cache --------------------------------------


def test_full_pass_equals_the_reference(built):
    cfg, shape, tree, model, params = built
    want = ref.logits(tree, jnp.asarray(TOKENS), shape)
    got = full_logits(model, params, TOKENS)
    np.testing.assert_allclose(got, want, atol=tolerance(cfg, want))


def test_prefill_then_cached_decode_equals_the_reference(built):
    """A prompt of 21 padded to 32, then 19 steps through the slot cache:
    the ring of 8 has wrapped four times by the end."""
    cfg, shape, tree, model, params = built
    want = ref.logits(tree, jnp.asarray(TOKENS), shape)[20:]
    got, _ = cached_logits(model, params, TOKENS, 21, 32)
    np.testing.assert_allclose(got, want, atol=tolerance(cfg, want))
    # the cached path is the full pass's arithmetic, whatever the weights
    full = full_logits(model, params, TOKENS)[20:]
    np.testing.assert_allclose(got, full, atol=1e-4 * float(jnp.std(want)))


def test_bfloat16_where_float32_is_stated_fails(f32):
    cfg, shape, tree, model, params = f32
    low = TransformerLM(dataclasses.replace(model.cfg, dtype=jnp.bfloat16))
    want = ref.logits(tree, jnp.asarray(TOKENS), shape)[20:]
    got, _ = cached_logits(low, params, TOKENS, 21, 32)
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert gap > 100 * tolerance(cfg, want)


def test_prefill_serves_one_position_of_the_full_pass(f32):
    """Layers past the full-attention layer run for ``p_len - 1`` alone."""
    cfg, shape, tree, model, params = f32
    full = full_logits(model, params, TOKENS)
    for p_len, bucket in ((5, 8), (21, 32), (32, 32), (33, 64)):
        got, _ = cached_logits(model, params, TOKENS[:p_len], p_len, bucket)
        np.testing.assert_allclose(
            got[0], full[p_len - 1], atol=tolerance(cfg, full))


@pytest.mark.parametrize("b, s, e, n", [(2, 37, 256, 4), (1, 300, 128, 16)])
def test_selective_scan_kernel_equals_the_plain_scan(b, s, e, n):
    """The Pallas scan (interpreted here) against ``lax.scan`` over the one
    step: the same sums a position, the read-out's in another order
    (4e-6 of values of a few units); a length that is no whole block is
    padded with ``delta = 0``."""
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.normal(size=(b, s, e)), jnp.float32)
    delta = jax.nn.softplus(jnp.asarray(rng.normal(size=(b, s, e)), jnp.float32))
    bm, cm = (jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32) for _ in "bc")
    a = -jnp.exp(jnp.asarray(rng.normal(size=(n, e)), jnp.float32))
    y, state = selective_scan(u, delta, a, bm, cm)
    y_ref, state_ref = selective_scan_reference(u, delta, a, bm, cm)
    assert y.shape == (b, s, e) and state.shape == (b, n, e)
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    np.testing.assert_allclose(state, state_ref, atol=2e-6)
    # a width that is no whole lane tile keeps the plain scan
    y_odd, _ = selective_scan(u[..., :96], delta[..., :96], a[:, :96], bm, cm)
    np.testing.assert_array_equal(
        y_odd, selective_scan_reference(
            u[..., :96], delta[..., :96], a[:, :96], bm, cm)[0])


@pytest.mark.parametrize("scan", [selective_scan, selective_scan_reference],
                         ids=["kernel", "plain"])
def test_selective_scan_stops_at_the_prompts_end_to_the_last_bit(scan):
    """``delta = 0`` is the identity on the state: ``exp(0 * A) = 1`` and
    ``(0 * u) B = 0`` exactly, so the state after a padded scan is the
    state after the prompt's own positions, bit for bit; and one step from
    it is the scan one position longer, to rounding."""
    rng = np.random.default_rng(0)
    b, s, e, n, p_len = 2, 32, 128, 4, 21
    u, c_in = (jnp.asarray(rng.normal(size=(b, s, e)), jnp.float32) for _ in "uc")
    delta = jax.nn.softplus(c_in)
    bm, cm = (jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32) for _ in "bc")
    a = -jnp.exp(jnp.asarray(rng.normal(size=(n, e)), jnp.float32))
    masked = jnp.where(jnp.arange(s)[None, :, None] < p_len, delta, 0.0)
    y_pad, s_pad = scan(u, masked, a, bm, cm)
    y_cut, s_cut = scan(
        u[:, :p_len], delta[:, :p_len], a, bm[:, :p_len], cm[:, :p_len])
    np.testing.assert_array_equal(s_pad, s_cut)
    np.testing.assert_array_equal(y_pad[:, :p_len], y_cut)
    s_next, y_next = selective_step(
        s_cut, u[:, p_len], delta[:, p_len], a, bm[:, p_len], cm[:, p_len])
    y_more, s_more = scan(
        u[:, :p_len + 1], delta[:, :p_len + 1], a, bm[:, :p_len + 1],
        cm[:, :p_len + 1])
    # (a step compiled alone fuses its multiply-adds otherwise: 2e-7)
    np.testing.assert_allclose(s_next, s_more, atol=1e-6)
    np.testing.assert_allclose(y_next, y_more[:, -1], atol=1e-5)
    assert float(jnp.max(jnp.abs(s_next - s_cut))) > 0.1


def test_padded_bucket_leaves_the_unpadded_prompts_state(f32):
    """Through the whole model: past ``p_len`` the state stands still, the
    convolution's tail is rows ``p_len - 3 .. p_len - 1`` and the rings
    hold the prompt's last 8 rows at ``t % 8``, whatever the bucket. The
    products of a bucket of 64 rows and of 21 round differently on the CPU
    (4e-7 on a row of K here), so the leaves agree to 2e-6 and not to the
    bit; a prompt one token longer moves them by hundreds of times that."""
    cfg, shape, tree, model, params = f32
    p_len = 21
    _, padded = cached_logits(model, params, TOKENS[:p_len], p_len, 64)
    _, exact = cached_logits(model, params, TOKENS[:p_len], p_len, p_len)
    _, longer = cached_logits(model, params, TOKENS[:p_len + 1], p_len + 1, 64)
    for name in ("ssm_state", "conv_state", "window_key", "window_value"):
        off = float(jnp.max(jnp.abs(padded[name] - exact[name])))
        moved = float(jnp.max(jnp.abs(longer[name] - exact[name])))
        assert off <= 2e-6 and moved > 100 * off, (name, off, moved)
    for name in ("shared_key", "shared_value"):
        np.testing.assert_allclose(
            padded[name][:, :, :, :p_len], exact[name][:, :, :, :p_len], atol=2e-6)
    assert int(padded["cache_index"]) == int(exact["cache_index"]) == p_len


def test_ring_equals_a_whole_cache_under_the_window_mask(f32):
    """Past a wrap a ring place holds the newest position with its
    remainder: decoding 30 positions through rings of 8 equals the full
    pass, whose window layers mask whole sequences."""
    cfg, shape, tree, model, params = f32
    assert model.cfg.sliding_window == 8 < 10 + 30
    full = full_logits(model, params, TOKENS)[9:]
    got, cache = cached_logits(model, params, TOKENS, 10, 16)
    np.testing.assert_allclose(got, full, atol=tolerance(cfg, full))
    assert cache["window_key"].shape[3] == 8


def test_decode_kernel_reads_rings_and_the_shared_cache_in_place(monkeypatch):
    """Head pairs of 128 and windows in whole blocks: the cached path runs
    ``ops.decode_attention`` (interpreted here) over stacks that hold a KV
    pair's rows together, and equals the full pass."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=256, n_layers=8, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq_len=256, norm_eps=1e-5, mb_per_layer=2,
        sliding_window=128, tie_embeddings=True, scan_layers=True,
        mamba_d_state=4,
    )
    model = TransformerLM(cfg)
    tokens = np.random.default_rng(5).integers(0, 128, 140)
    params = model.init(jax.random.PRNGKey(2), jnp.asarray(tokens[None]))["params"]
    calls = []
    kernel = decode_attention_module.decode_attention
    monkeypatch.setattr(
        decode_attention_module, "decode_attention",
        lambda q, k, *a, **kw: calls.append((k.shape, kw)) or kernel(q, k, *a, **kw))
    full = full_logits(model, params, tokens)[129:]
    got, _ = cached_logits(model, params, tokens, 130, 256)
    np.testing.assert_allclose(got, full, atol=1e-4)
    # a period's ring, the shared cache's own layer and a cross layer, in
    # the prefill's one position and in the step: stacks with a KV pair's
    # rows together
    assert {shape for shape, _ in calls} == {
        (2, 1, 1, 128, 128), (1, 1, 1, 256, 128)}
    assert all(kw == {"heads_major": True} for _, kw in calls)


# -- ServeEngine -----------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    cfg = toy_config(weights_dtype="int8", compute_dtype="bfloat16",
                     kv_cache_dtype="bfloat16")
    shape = ref.Shape.from_config(cfg)
    tree = weights.make(ref.leaf_shapes(shape), 9, "int8", 0.05)  # the rehearsal's
    model = BLOCK.program.model(cfg, "serve", WINDOW)
    return model, BLOCK.program.to_program(tree, shape)


def test_engine_equals_generate_a_request_at_a_time(served):
    """Two slots, four requests of other lengths: slots at different
    depths in one chain, and a slot refilled after another request."""
    model, params = served
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=4)
    rng = np.random.default_rng(0)
    requests = [
        Request(prompt=[int(t) for t in rng.integers(0, 512, n)],
                max_new_tokens=m)
        for n, m in [(5, 9), (23, 12), (40, 6), (17, 20)]
    ]
    ids = [engine.submit(r) for r in requests]
    done = {c.request_id: c for c in engine.run_until_idle()}
    assert engine.n_prefills == 4
    for rid, r in zip(ids, requests):
        want = generate(
            model, params, jnp.asarray([r.prompt], jnp.int32), r.max_new_tokens
        )[0, len(r.prompt):]
        assert list(done[rid].tokens) == [int(t) for t in want]


def test_engine_counts_the_bytes_a_slot_holds(served):
    model, params = served
    engine = ServeEngine(model, params, n_slots=3, tokens_per_launch=4)
    c = model.cfg
    e, n, _, taps = sambay.mamba_sizes(c)
    row = c.kv_heads * c.head_dim * 2 * 2  # K and V of a position, bfloat16
    assert engine.stats("slot") == {
        "slot_kv_bytes": WINDOW * row,
        "slot_ring_bytes": (c.n_layers // 4) * c.sliding_window * row,
        "slot_state_bytes": (c.n_layers // 4 + 1) * e * (n + taps - 1) * 4,
    }
    assert engine.stats()["slot_state_bytes"] > 0
    # a model of K and V alone holds neither state nor ring
    plain = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, max_seq_len=16))
    cache = jax.eval_shape(
        lambda: plain.init(jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
                           decode=True)["cache"])
    assert slot_bytes(cache, 2) == {
        "slot_kv_bytes": 2 * 2 * 16 * 32 * 4, "slot_ring_bytes": 0,
        "slot_state_bytes": 0}


@pytest.mark.parametrize("options,words", [
    (dict(paged=True, page_size=16, pool_pages=8), "paged=True"),
    (dict(prefix_cache_bytes=1 << 20), "prefix_cache_bytes"),
    (dict(prefill_chunk=16), "prefill_chunk"),
    (dict(speculative_k=2), "speculative_k"),
    (dict(kv_bits=8), "kv_bits"),
    (dict(priority_classes=2), "priority_classes"),
    (dict(role="prefill"), "role"),
])
def test_engine_refuses_in_words(served, options, words):
    model, params = served
    with pytest.raises(ValueError, match="recurrent state.*whole slots only") as e:
        ServeEngine(model, params, n_slots=2, **options)
    assert words in str(e.value)


def test_engine_refuses_a_tensor_parallel_strategy_and_an_adapter_bank(served):
    model, params = served

    class Strategy:  # what the engine reads of one
        tp_size = 2
        mesh = None

        def shard_state(self, tree):
            return tree

    with pytest.raises(ValueError, match="tensor-parallel strategy"):
        ServeEngine(model, params, n_slots=2, strategy=Strategy())

    class Bank:
        model = served[0]
        version = 0

        def merge_params(self, p):
            return p

    with pytest.raises(ValueError, match="an adapter bank"):
        ServeEngine(model, params, n_slots=2, adapter_bank=Bank())


SOUND = dict(
    vocab_size=64, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2, d_ff=64,
    max_seq_len=32, mb_per_layer=2, sliding_window=8, tie_embeddings=True,
    scan_layers=True,
)


@pytest.mark.parametrize("change,words", [
    (dict(mb_per_layer=3), "must be 2"),
    (dict(n_layers=6), "multiple of 4"),
    (dict(sliding_window=0), "sliding_window >= 1"),
    (dict(tie_embeddings=False), "tie_embeddings=True"),
    (dict(n_heads=3, n_kv_heads=3, d_model=48), "must be even"),
    (dict(scan_layers=False), "scan_layers=True"),
    (dict(kv_cache_dtype="int8"), "as floats"),
    (dict(kv_pages=4, kv_page_size=8), "paged KV cache"),
    (dict(lora_adapters=2, lora_rank=2), "LoRA adapters"),
    (dict(attention_fn=lambda q, k, v: q), "custom attention_fn"),
    (dict(n_routed_experts=4, experts_per_token=2, expert_d_ff=8), "routed experts"),
    (dict(remat=True), "remat"),
])
def test_model_refuses_in_words(change, words):
    model = TransformerLM(TransformerConfig(**{**SOUND, **change}))
    with pytest.raises(ValueError, match=words):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_tied_embeddings_belong_to_this_layout():
    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, tie_embeddings=True))
    with pytest.raises(ValueError, match="tie_embeddings is the head"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_a_decode_chunk_has_no_state_to_rewind_to():
    model = TransformerLM(TransformerConfig(**SOUND))
    tokens = jnp.zeros((1, 4), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    with pytest.raises(ValueError, match="one position at a time"):
        model.apply({"params": params}, tokens, decode=True, mutable=["cache"])


# -- int8 serving of a trained tree ---------------------------------------------


def test_quantize_ties_the_head_to_the_embedding():
    """``quantize_lm_params`` on the float model: every product's kernel in
    int8 a layer of each scan, the head the embedding's transpose with one
    scale a vocabulary row, and the embedding those values dequantized."""
    cfg = TransformerConfig(**{**SOUND, "d_model": 128, "d_ff": 128})
    model = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 64, (1, 12)))
    params = model.init(jax.random.PRNGKey(4), tokens)["params"]
    q = quantize_lm_params(params)
    head, table = q["lm_head"], q["tok_emb"]["embedding"]
    assert head["q"].shape == (128, 64) and head["q"].dtype == jnp.int8
    np.testing.assert_array_equal(
        table, (head["q"].astype(jnp.float32) * head["scale"]).T)
    np.testing.assert_allclose(table, params["tok_emb"]["embedding"], atol=0.05)
    stack = q["layers_a"]["mamba_block"]["mixer"]["in_proj"]
    assert stack["q"].shape == (2, 128, 512) and stack["scale"].shape == (2, 1, 512)
    one = quantize_lm_params(
        {"in_proj": {"kernel": params["layers_a"]["mamba_block"]["mixer"]
                     ["in_proj"]["kernel"][1]}})["in_proj"]
    np.testing.assert_array_equal(stack["q"][1], one["q"])  # a layer its own scales
    assert q["layers_a"]["mamba_block"]["mixer"]["A_log"].dtype == jnp.float32
    served = TransformerLM(dataclasses.replace(cfg, quantized=True))
    theirs = jax.eval_shape(served.init, jax.random.PRNGKey(0), tokens)["params"]
    assert jax.tree_util.tree_structure(theirs) == jax.tree_util.tree_structure(q)
    got = served.apply({"params": q}, tokens)
    want = model.apply({"params": params}, tokens)
    assert float(jnp.max(jnp.abs(got - want))) < 0.2 * float(jnp.std(want))
    assert quantize_lm_params(params, jnp.bfloat16)["tok_emb"][
        "embedding"].dtype == jnp.bfloat16

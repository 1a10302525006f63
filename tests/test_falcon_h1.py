"""A Mamba-2 mixer and attention side by side in every block
(``models/mamba2.py``, ``ops/ssd.py``; ISSUE 36) against the plain reference
(``benchmark/blocks/falcon_h1/reference.py``: float32, a recurrence a
position, no cache), at toy widths on the CPU with weights from a seed: the
kernels alone, the whole model's full pass, prefill and decode through the
slot tree ``ServeEngine`` keeps (logits, not tokens), the state of a padded
bucket, two slots at different depths, ``ServeEngine`` itself, every
multiplier dropped in turn, the planted faults, and every refusal's words.

Tolerances, in units of the logits' deviation. Float32 through the cache
against the reference at ``HIGHEST``: both compute the same sums in float32
in another order (the chunked form sums a chunk at a time): **1e-4**, which
bfloat16 compute fails by two orders. int8 weights under W8A8: each
activation row is rounded to 8 bits a K tile through 2 layers: limit
**0.1**; the cached path against the program's own full pass is the same
arithmetic whatever the weights and holds 1e-3.
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
    mamba2,
    quantize_lm_params,
)
from pytorch_distributed_training_tutorials_tpu.models.transformer import (  # noqa: E402
    park_cache_index,
)
from pytorch_distributed_training_tutorials_tpu.ops import ssd  # noqa: E402
from pytorch_distributed_training_tutorials_tpu.serve import (  # noqa: E402
    Request,
    ServeEngine,
)
from pytorch_distributed_training_tutorials_tpu.serve.slots import (  # noqa: E402
    init_slot_state,
    slot_bytes,
    write_slot,
)

BLOCK = harness.Block("falcon_h1")
ref = BLOCK.reference
WINDOW = 128
F32_TOL, INT8_TOL = 1e-4, 0.1  # of the logits' deviation


def toy_config(**over):
    cfg = harness.read_json(os.path.join(
        harness.BENCH, "configs", "falcon-h1-34b-instruct-6of72.json"))
    for k, v in cfg["rehearse"].items():
        cfg[k] = {**cfg[k], **v} if isinstance(v, dict) else v
    # the one published multiplier that is 1: a dropped one has to show
    cfg["attention_in_multiplier"] = 0.5
    cfg["serve"] = dict(cfg["serve"], compute_dtype="float32",
                        kv_cache_dtype="float32", weights_dtype="float32")
    cfg["serve"].update(over)
    return cfg


def build(weights_dtype="float32", seed=7, **config_over):
    cfg = toy_config(weights_dtype=weights_dtype)
    cfg.update(config_over)
    shape = ref.Shape.from_config(cfg)
    tree = weights.make(
        ref.leaf_shapes(shape), seed, weights_dtype, cfg["initializer_range"])
    model = BLOCK.program.model(cfg, "serve", WINDOW)
    return cfg, shape, tree, model, BLOCK.program.to_program(tree, shape)


@pytest.fixture(scope="module", params=["float32", "int8"])
def built(request):
    return build(request.param)


@pytest.fixture(scope="module")
def f32():
    return build()


TOKENS = np.random.default_rng(3).integers(0, 512, 40)


def tolerance(cfg, logits) -> float:
    share = F32_TOL if cfg["serve"]["weights_dtype"] == "float32" else INT8_TOL
    return share * float(jnp.std(logits))


@functools.lru_cache(maxsize=None)
def _programs(model):
    prefill = jax.jit(lambda p, t, last: model.apply(
        {"params": p}, t, prefill=True, mutable=["cache"], last_pos=last))
    step = jax.jit(lambda p, c, t: model.apply(
        {"params": p, "cache": c}, t, decode=True, mutable=["cache"]))
    full = jax.jit(lambda p, t: model.apply({"params": p}, t))
    return prefill, step, full


def full_logits(model, params, tokens):
    return _programs(model)[2](params, jnp.asarray(tokens[None]))[0]


def slot_logits(model, params, tokens, p_len, bucket, n_slots=3, slot=1,
                splice=write_slot):
    """Logits at positions ``p_len - 1 ..`` of a request served from slot
    ``slot`` of ``n_slots``, as ``ServeEngine`` serves it: a batch-1 prefill
    of ``p_len`` tokens right-padded to ``bucket``, spliced into the slot
    tree, then one decode step a token with every other slot parked.
    Returns ``(logits, the prefilled batch-1 cache, the slot tree)``."""
    prefill, step, _ = _programs(model)
    pad = np.zeros((1, bucket), np.int32)
    pad[0, :p_len] = tokens[:p_len]
    lg, upd = prefill(params, jnp.asarray(pad), p_len - 1)
    state = init_slot_state(model, params, n_slots)
    cache = park_cache_index(
        state["cache"], jnp.ones((n_slots,), bool), model.cfg.max_seq_len)
    cache = splice(cache, upd["cache"], slot, p_len, True)
    out = [lg[0, 0]]
    parked = jnp.arange(n_slots) != slot
    for t in range(p_len, len(tokens)):
        toks = np.zeros((n_slots, 1), np.int32)
        toks[slot, 0] = tokens[t]
        # as the chain does before every step
        cache = park_cache_index(cache, parked, model.cfg.max_seq_len)
        lg, new = step(params, cache, jnp.asarray(toks))
        out.append(lg[slot, 0])
        cache = new["cache"]
    return jnp.stack(out), upd["cache"], cache


# -- the kernels alone -------------------------------------------------------


def _ssd_inputs(b, s, h, p, g, n, seed=1):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(f(b, s, h) - 2.0)
    a = -jnp.exp(f(h))
    return f(b, s, h, p), dt, a, f(b, s, g, n), f(b, s, g, n)


@pytest.mark.parametrize("b, s, h, p, g, n, chunk", [
    (2, 37, 4, 8, 2, 6, 8), (1, 64, 6, 16, 3, 4, 16), (1, 5, 2, 4, 1, 3, 8)])
def test_chunked_form_equals_the_recurrence(b, s, h, p, g, n, chunk):
    """Matrix products inside a chunk and over the chunks' states against
    the recurrence a position: the same sums in another order; a length
    that is no whole chunk is padded with ``dt = 0``."""
    x, dt, a, bm, cm = _ssd_inputs(b, s, h, p, g, n)
    y, last = ssd.ssd_chunked(x, dt, a, bm, cm, chunk)
    y_ref, last_ref = ssd.ssd_recurrence(x, dt, a, bm, cm)
    assert y.shape == (b, s, h, p) and last.shape == (b, h, n, p)
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    np.testing.assert_allclose(last, last_ref, atol=2e-5)


def test_chunked_form_stops_at_the_prompts_end():
    """``dt = 0`` is the identity on the state: the state after a padded
    prompt is the state after its own positions (the products of 32 and of
    21 rows round differently: 1e-6, where one more position moves it by
    thousands of times that)."""
    x, dt, a, bm, cm = _ssd_inputs(2, 32, 4, 8, 2, 6)
    p_len = 21
    masked = jnp.where(jnp.arange(32)[None, :, None] < p_len, dt, 0.0)
    _, padded = ssd.ssd_chunked(x, masked, a, bm, cm, 8)
    cut = lambda t, n: t[:, :n]  # noqa: E731
    _, exact = ssd.ssd_chunked(*(cut(t, p_len) for t in (x, dt)), a,
                               *(cut(t, p_len) for t in (bm, cm)), 8)
    _, longer = ssd.ssd_chunked(*(cut(t, p_len + 1) for t in (x, dt)), a,
                                *(cut(t, p_len + 1) for t in (bm, cm)), 8)
    off = float(jnp.max(jnp.abs(padded - exact)))
    assert off <= 2e-6 and float(jnp.max(jnp.abs(longer - exact))) > 1000 * off


@pytest.mark.parametrize("pos", [
    [0, 3, 9, 2, 1], [16, 3, 16, 16, 1], [16, 16, 2, 16, 16], [16] * 5])
def test_ssd_update_equals_the_plain_step_in_place(pos):
    """The Pallas step (interpreted here) on layer 1 of a stack of three:
    live slots' states and results to float32 rounding of the plain step's
    (the read-out sums in another order), dead slots' states (depth = the
    window, 16) untouched and their results zero, the other layers'
    states bit for bit what they were."""
    L, B, H, N, P, G = 3, 5, 4, 16, 128, 2
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    stack, dtx, bm, cm = f(L, B, H, N, P), f(B, H, P), f(B, G, N), f(B, G, N)
    decay = jnp.exp(-jnp.abs(f(B, H)))
    pos = jnp.asarray(pos, jnp.int32)
    y, out = jax.jit(lambda s, l, at: ssd.ssd_update(
        s, l, decay, dtx, bm, cm, at, 16))(stack, 1, pos)
    want, y_want = ssd.ssd_step(stack[1], decay, dtx, bm, cm)
    np.testing.assert_array_equal(out[0], stack[0])
    np.testing.assert_array_equal(out[2], stack[2])
    for slot, depth in enumerate(np.asarray(pos)):
        if depth < 16:
            np.testing.assert_allclose(out[1, slot], want[slot], atol=1e-6)
            np.testing.assert_allclose(y[slot], y_want[slot], atol=2e-5)
        else:
            np.testing.assert_array_equal(out[1, slot], stack[1, slot])
            assert not np.asarray(y[slot]).any()


def test_ssd_kernel_takes_whole_tiles_only():
    assert ssd.ssd_heads_block(16, 256, 128) == 8  # 1 MB of state a step
    assert ssd.ssd_heads_block(2, 16, 128) == 2
    assert ssd.ssd_heads_block(2, 8, 16) is None  # toy widths: the plain step
    assert ssd.ssd_heads_block(2, 6, 128) is None


# -- the mixer and the whole model ---------------------------------------------


def test_mixer_equals_the_reference(f32):
    from benchmark.blocks.gqa_swiglu.reference import linear

    cfg, shape, tree, model, params = f32
    lin = functools.partial(linear, precision="float32", weight_bits=8)
    layer = jax.tree_util.tree_map(lambda t: t[1], ref.drawn(tree)["layers"])
    mine = jax.tree_util.tree_map(
        lambda t: t[1], params["layers"]["block"]["mamba"])
    u = jnp.asarray(np.random.default_rng(11).normal(
        size=(24, shape.hidden_size)), jnp.float32)
    want = ref.mamba2(u, layer, shape, lin)
    got = mamba2.Mamba2Mixer(model.cfg).apply({"params": mine}, u[None])
    np.testing.assert_allclose(got[0], want, atol=2e-6 + 1e-4 * float(jnp.std(want)))


def test_full_pass_equals_the_reference(built):
    cfg, shape, tree, model, params = built
    want = ref.logits(tree, jnp.asarray(TOKENS), shape)
    got = full_logits(model, params, TOKENS)
    np.testing.assert_allclose(got, want, atol=tolerance(cfg, want))


def test_prefill_then_decode_through_a_slot_equals_the_reference(built):
    """A prompt of 21 padded to 32 (three chunks of 8, the third cut by
    ``p_len``), spliced into slot 1 of 3, then 19 steps with the other
    slots parked."""
    cfg, shape, tree, model, params = built
    want = ref.logits(tree, jnp.asarray(TOKENS), shape)[20:]
    got, _, _ = slot_logits(model, params, TOKENS, 21, 32)
    np.testing.assert_allclose(got, want, atol=tolerance(cfg, want))
    # the cached path is the full pass's arithmetic, whatever the weights
    full = full_logits(model, params, TOKENS)[20:]
    np.testing.assert_allclose(got, full, atol=1e-3 * float(jnp.std(want)))


def test_bfloat16_where_float32_is_stated_fails(f32):
    cfg, shape, tree, model, params = f32
    low = TransformerLM(dataclasses.replace(model.cfg, dtype=jnp.bfloat16))
    want = ref.logits(tree, jnp.asarray(TOKENS), shape)[20:]
    got, _, _ = slot_logits(low, params, TOKENS, 21, 32)
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert gap > 100 * tolerance(cfg, want)


def test_padded_bucket_leaves_the_unpadded_prompts_state(f32):
    """Past ``p_len`` the state stands still and the convolution's tail is
    rows ``p_len - 3 .. p_len - 1``, whatever the bucket; K and V hold the
    prompt's rows. The products of a bucket of 64 rows and of 21 round
    differently on the CPU, so the leaves agree to 2e-6 (K and V, values of
    up to 7: 2e-5) and not to the bit;
    a prompt one token longer moves them by hundreds of times that."""
    cfg, shape, tree, model, params = f32
    p_len = 21
    leaves = lambda c: c["layers"]["block"]  # noqa: E731
    padded = leaves(slot_logits(model, params, TOKENS[:p_len], p_len, 64)[1])
    exact = leaves(slot_logits(model, params, TOKENS[:p_len], p_len, p_len)[1])
    longer = leaves(
        slot_logits(model, params, TOKENS[:p_len + 1], p_len + 1, 64)[1])
    for name in ("ssm_state", "conv_state"):
        a, b, c = (t["mamba"][name] for t in (padded, exact, longer))
        off = float(jnp.max(jnp.abs(a - b)))
        moved = float(jnp.max(jnp.abs(c - b)))
        assert off <= 2e-6 and moved > 100 * off, (name, off, moved)
    for name in ("cached_key", "cached_value"):
        # (L, 1, KV, W, D): a KV head's rows together
        assert padded["attn"][name].shape == (2, 1, 2, WINDOW, 16)
        np.testing.assert_allclose(
            padded["attn"][name][:, :, :, :p_len],
            exact["attn"][name][:, :, :, :p_len], atol=2e-5)


def test_two_slots_at_different_depths_keep_to_themselves(f32):
    """Slot 0 serves one request from depth 9, slot 2 another from depth
    21, stepped together: each reads what it reads served alone."""
    cfg, shape, tree, model, params = f32
    prefill, step, _ = _programs(model)
    other = np.random.default_rng(5).integers(0, 512, 40)
    alone_a, _, _ = slot_logits(model, params, TOKENS[:20], 9, 16, slot=0)
    alone_b, _, _ = slot_logits(model, params, other[:32], 21, 32, slot=2)
    state = init_slot_state(model, params, 3)
    cache = park_cache_index(state["cache"], jnp.ones((3,), bool), WINDOW)
    firsts = []
    for slot, toks, p_len, bucket in ((0, TOKENS, 9, 16), (2, other, 21, 32)):
        pad = np.zeros((1, bucket), np.int32)
        pad[0, :p_len] = toks[:p_len]
        lg, upd = prefill(params, jnp.asarray(pad), p_len - 1)
        cache = write_slot(cache, upd["cache"], slot, p_len, True)
        firsts.append(lg[0, 0])
    np.testing.assert_allclose(firsts[0], alone_a[0], atol=1e-6)
    np.testing.assert_allclose(firsts[1], alone_b[0], atol=1e-6)
    for i in range(11):
        toks = np.zeros((3, 1), np.int32)
        toks[0, 0], toks[2, 0] = TOKENS[9 + i], other[21 + i]
        cache = park_cache_index(cache, jnp.asarray([False, True, False]), WINDOW)
        lg, new = step(params, cache, jnp.asarray(toks))
        cache = new["cache"]
        np.testing.assert_allclose(lg[0, 0], alone_a[1 + i], atol=2e-6)
        np.testing.assert_allclose(lg[2, 0], alone_b[1 + i], atol=2e-6)
    # (the parked slot steps on junk here: at toy widths the plain step
    # runs; the kernel leaves it alone, test_ssd_update_equals_...)
    mamba = cache["layers"]["block"]["mamba"]
    assert mamba["cache_index"].tolist() == [[20, WINDOW + 1, 32]] * 2


MULTIPLIERS = [
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier",
    *(f"ssm_multipliers.{i}" for i in range(5)),
    "mlp_multipliers.0", "mlp_multipliers.1",
]


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_a_dropped_multiplier_fails(f32, name):
    """The eleven published keys hold fourteen numbers; the program with
    any one of them left out (1.0) is off the reference by hundreds of
    times the tolerance it otherwise holds."""
    cfg, shape, tree, model, params = f32
    field, _, at = name.partition(".")
    value = getattr(model.cfg, field)
    assert (value[int(at)] if at else value) != 1.0
    if at:
        value = tuple(1.0 if i == int(at) else m for i, m in enumerate(value))
    else:
        value = 1.0
    broken = TransformerLM(dataclasses.replace(model.cfg, **{field: value}))
    want = ref.logits(tree, jnp.asarray(TOKENS), shape)[20:]
    got, _, _ = slot_logits(broken, params, TOKENS, 21, 32)
    gap = float(jnp.max(jnp.abs(got - want)))
    assert gap > 100 * tolerance(cfg, want), (name, gap)


def _zero_state_splice(cache, pre, slot, p_len, scan_layers):
    """``write_slot`` that splices K and V and forgets the prefilled state
    and the convolution's tail: decode starts from zeros."""
    def wipe(path, leaf):
        name = str(path[-1])
        forgot = "ssm_state" in name or "conv_state" in name
        return jnp.zeros_like(leaf) if forgot else leaf

    return write_slot(
        cache, jax.tree_util.tree_map_with_path(wipe, pre), slot, p_len,
        scan_layers)


@pytest.mark.parametrize("fault", ["gate_after_norm", "zero_state"])
def test_a_planted_fault_fails(f32, fault, monkeypatch, request):
    """The gate applied after the grouped norm; decode starting from a zero
    state (the prefilled state not spliced into the slot): each off the
    reference by tens of times the tolerance."""
    cfg, shape, tree, model, params = f32
    splice = write_slot
    if fault == "gate_after_norm":
        def norm_then_gate(y, z, weight, groups, eps):
            grouped = y.reshape(*y.shape[:-1], groups, -1)
            normed = grouped * jax.lax.rsqrt(
                jnp.mean(grouped * grouped, -1, keepdims=True) + eps)
            return normed.reshape(y.shape) * weight * jax.nn.silu(z)

        monkeypatch.setattr(mamba2, "gate_and_norm", norm_then_gate)
        _programs.cache_clear()  # programs traced with the sound mixer
        request.addfinalizer(_programs.cache_clear)
    else:
        splice = _zero_state_splice
    want = ref.logits(tree, jnp.asarray(TOKENS), shape)[20:]
    got, _, _ = slot_logits(model, params, TOKENS, 21, 32, splice=splice)
    gap = float(jnp.max(jnp.abs(got[1:] - want[1:])))
    assert gap > 30 * tolerance(cfg, want), (fault, gap)


# -- ServeEngine ---------------------------------------------------------------


def _greedy_reference(tree, shape, prompt, n_new):
    """Greedy tokens of the reference, and each one's margin over the
    runner-up in deviations of its position's logits."""
    seq, margins = list(prompt), []
    for _ in range(n_new):
        lg = ref.logits(tree, jnp.asarray(seq), shape,
                        positions=jnp.asarray([len(seq) - 1]))[0]
        top = jnp.sort(lg)[-2:]
        margins.append(float((top[1] - top[0]) / jnp.std(lg)))
        seq.append(int(jnp.argmax(lg)))
    return seq[len(prompt):], margins


def test_engine_serves_the_references_tokens(f32):
    """Requests of several lengths through two slots of ``ServeEngine``
    (refills included): every served token is the reference's greedy token
    wherever the reference's margin is not a near tie, and equals
    ``generate()``'s."""
    cfg, shape, tree, model, params = f32
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=4)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 512, n).tolist() for n in (5, 13, 9, 21)]
    ids = [engine.submit(Request(prompt=p, max_new_tokens=7)) for p in prompts]
    done = {c.request_id: c for c in engine.run_until_idle()}
    for rid, prompt in zip(ids, prompts):
        got = list(done[rid].tokens)
        want, margins = _greedy_reference(tree, shape, prompt, 7)
        for i, (g, w, m) in enumerate(zip(got, want, margins)):
            if g != w:
                assert m < 1e-3, (prompt, i, m)
                break
        one = generate(model, params, jnp.asarray([prompt], jnp.int32), 7)
        assert got == np.asarray(one)[0, len(prompt):].tolist()
    stats = engine.stats("slot")
    cache = engine._state["cache"]  # noqa: SLF001
    assert stats == slot_bytes(cache, 2)
    # K and V of two layers, 2 heads x 128 rows x 16; state 4 x 8 x 16 and
    # a tail of 3 x (64 + 2 x 2 x 8), float32
    assert stats["slot_kv_bytes"] == 2 * 2 * 2 * 128 * 16 * 4
    assert stats["slot_state_bytes"] == 2 * (4 * 8 * 16 + 3 * 96) * 4
    assert stats["slot_ring_bytes"] == 0


def test_engine_runs_both_kernels_where_the_sizes_are_whole_tiles():
    """Heads of 128, a state of 16 x 128, a window of one block: the chain
    runs ``decode_attention`` over K and V with a head's rows together and
    ``ssd_update`` on the carried stack (both interpreted here), int8
    weights through ``quantize_lm_params``, and serves ``generate()``'s
    tokens."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=2, n_kv_heads=1,
        d_head=128, d_ff=128, max_seq_len=128, scan_layers=True,
        mamba_n_heads=2, mamba_d_head=128, mamba_n_groups=1, mamba_d_state=16,
        mamba_chunk_size=8, ssm_out_multiplier=0.5, key_multiplier=0.25,
        mlp_multipliers=(0.5, 0.25), quantized=True,
    )
    assert cfg.kv_heads_major and ssd.ssd_heads_block(2, 16, 128)
    plain = TransformerLM(dataclasses.replace(cfg, quantized=False))
    params = plain.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    params = quantize_lm_params(params["params"])
    model = TransformerLM(cfg)
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=3)
    prompts = [[3, 9, 27, 81, 115], [5, 25, 125, 113, 53, 9, 45, 97, 101]]
    ids = [engine.submit(Request(prompt=p, max_new_tokens=5)) for p in prompts]
    done = {c.request_id: c for c in engine.run_until_idle()}
    for rid, prompt in zip(ids, prompts):
        one = generate(model, params, jnp.asarray([prompt], jnp.int32), 5)
        assert list(done[rid].tokens) == np.asarray(one)[0, len(prompt):].tolist()


# -- refusals --------------------------------------------------------------------


def test_refusals_in_words(f32):
    cfg, shape, tree, model, params = f32
    base = model.cfg
    tokens = jnp.zeros((1, 8), jnp.int32)

    def refused(match, **over):
        with pytest.raises(ValueError, match=match):
            TransformerLM(dataclasses.replace(base, **over)).init(
                jax.random.PRNGKey(0), tokens)

    refused("two layouts", mb_per_layer=2)
    refused("scan_layers=True", scan_layers=False)
    refused("mamba_n_groups", mamba_n_groups=3)
    refused("kv_cache_dtype", kv_cache_dtype=jnp.int8)
    refused("recurrent state.*paged", kv_pages=4, kv_page_size=8)
    refused("recurrent state.*LoRA", lora_adapters=2, lora_rank=2)
    refused("recurrent state.*remat", remat=True)
    refused("ssm_multipliers are five", ssm_multipliers=(1.0, 1.0))
    with pytest.raises(ValueError, match="one position at a time"):
        state = init_slot_state(model, params, 2)
        model.apply({"params": params, "cache": state["cache"]},
                    jnp.zeros((2, 3), jnp.int32), decode=True, mutable=["cache"])
    assert base.recurrent and TransformerConfig(mb_per_layer=2).recurrent
    assert not TransformerConfig().recurrent
    for kw, words in (
        (dict(paged=True, page_size=8, pool_pages=64), "paged=True"),
        (dict(prefix_cache_bytes=1 << 20), "prefix_cache_bytes"),
        (dict(prefill_chunk=8), "prefill_chunk"),
        (dict(speculative_k=2), "speculative_k"),
        (dict(kv_bits=8), "kv_bits"),
        (dict(priority_classes=2), "priority_classes"),
        (dict(role="prefill"), "role"),
    ):
        with pytest.raises(ValueError, match="recurrent state.*whole slots only") as e:
            ServeEngine(model, params, n_slots=2, **kw)
        assert words in str(e.value)


def test_stated_head_dim_defaults_to_the_quotient():
    assert TransformerConfig(d_model=128, n_heads=4).head_dim == 32
    assert TransformerConfig(d_model=96, n_heads=4, d_head=16).head_dim == 16
    assert not TransformerConfig(n_heads=4, n_kv_heads=2).kv_heads_major

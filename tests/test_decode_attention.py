"""Decode attention that reads K and V in the carried stack (ISSUE 31).

``ops.decode_attention`` runs in interpret mode here; what it must equal is
the plain path it replaces, ``grouped_masked_attention`` over one layer's
window. The model-level cases run ``Attention`` at ``head_dim`` 128, the
only width at which its decode branch takes the kernel (the other tests'
toy widths keep the plain einsums), and hold the kernel's path to its
tolerance and to equal greedy tokens against the prefill-only forward,
which never runs it. The engine case serves more requests than slots, so
slots fall empty (their depth pinned to the window: ``park_cache_index``)
and are refilled (the refill's real depth) in between.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tutorials_tpu.models.generate import generate
from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    grouped_masked_attention,
    park_cache_index,
)
from pytorch_distributed_training_tutorials_tpu.ops.decode_attention import (
    block_bounds,
    block_walk,
    decode_attention,
    decode_block,
)
from pytorch_distributed_training_tutorials_tpu.serve import (
    Request,
    ServeEngine,
)

W, BLOCK, D = 512, 256, 128


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("h, kv", [(2, 2), (4, 2), (8, 2)],
                         ids=["1to1", "2to1", "4to1"])
def test_kernel_matches_plain_attention(h, kv, dtype, layer):
    """A stack of 3 layers read at ``layer``; slots at depths 0, block - 1,
    block, W - 1 and one dead slot (depth W) in the middle: zeros out, and
    the others unchanged whatever its rows hold."""
    keys = jax.random.split(jax.random.PRNGKey(h), 3)
    pos = jnp.array([0, BLOCK - 1, W, BLOCK, W - 1], jnp.int32)
    b = pos.shape[0]
    q = jax.random.normal(keys[0], (b, h, D), jnp.float32)
    k = jax.random.normal(keys[1], (3, b, W, kv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(keys[2], (3, b, W, kv, D), jnp.float32).astype(dtype)
    out = decode_attention(q, k, v, jnp.int32(layer), pos, block_w=BLOCK)
    assert out.shape == (b, h, D) and out.dtype == q.dtype
    valid = jnp.arange(W)[None, None, :] <= pos[:, None, None]
    ref = grouped_masked_attention(
        q[:, None], k[layer], v[layer], valid[:, None]
    )[:, 0]
    live = np.asarray(pos < W)
    # bfloat16: the plain path rounds the softmax weights to the cache's
    # dtype before the second product, the interpreter computes in float32
    tol = 2e-6 if dtype == jnp.float32 else 4e-3
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=0)
    assert not np.asarray(out[~live]).any()
    # the dead slot's rows are never read: poison them, nothing moves
    again = decode_attention(
        q, k.at[:, 2].set(jnp.nan), v.at[:, 2].set(jnp.nan),
        jnp.int32(layer), pos, block_w=BLOCK,
    )
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))


@pytest.mark.parametrize("h, kv", [(4, 2), (40, 10)], ids=["2to1", "40on10"])
def test_heads_major_stack_gives_the_same_result(h, kv):
    """A stack (L, B, KV, W, D), a KV head's rows together (ISSUE 34: ten
    KV pairs are no whole sublane tile), read by the same kernel: what it
    gives for the same numbers with the positions first."""
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    pos = jnp.array([3, BLOCK, W, W - 1], jnp.int32)
    b = pos.shape[0]
    q = jax.random.normal(keys[0], (b, h, D), jnp.float32)
    k = jax.random.normal(keys[1], (2, b, W, kv, D), jnp.float32)
    v = jax.random.normal(keys[2], (2, b, W, kv, D), jnp.float32)
    want = decode_attention(q, k, v, jnp.int32(1), pos, block_w=BLOCK)
    got = decode_attention(
        q, jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3), jnp.int32(1), pos,
        block_w=BLOCK, heads_major=True,
    )
    # the same scores and weights; a block's rows are summed in another order
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert not np.asarray(got[2]).any()  # the dead slot


P = W  # a parked slot's depth


@pytest.mark.parametrize(
    "pos, block_w, heads_major, layer",
    [
        # at and one row either side of every boundary of blocks of 128
        ([127, 128, 129, 255, 256, 257, 383, 384, 385, 511], 128, False, 0),
        ([P, P, P, P], BLOCK, False, 0),
        ([P, P, P, 300], BLOCK, False, 0),
        # runs of parked slots between the live ones: each live slot's first
        # block is the one fetched while the live slot before it ends
        ([P, 5, P, P, P, 300, P, P, 129, P, P], 128, False, 0),
        # a ring of 512 rows at blocks of 256, a KV head's rows together
        ([P, 511, 0, P, 256, 255, P], BLOCK, True, 0),
        ([40, P, 400, 200], 128, False, 2),
    ],
    ids=["boundaries", "all_parked", "last_live", "parked_runs",
         "heads_major_ring", "layer2"],
)
def test_kernel_walks_only_the_live_blocks(pos, block_w, heads_major, layer):
    """The kernel walks each live slot's blocks up to its depth and no
    block of a parked one, fetching the next live block while it computes
    the one before: the plain path's result for every live slot, zeros for
    every parked one, and the blocks it must not read (past the one that
    holds a depth, every block of a parked slot) may hold NaN."""
    pos = jnp.array(pos, jnp.int32)
    b, h, kv = pos.shape[0], 8, 4
    keys = jax.random.split(jax.random.PRNGKey(b), 3)
    q = jax.random.normal(keys[0], (b, h, D), jnp.float32)
    k = jax.random.normal(keys[1], (3, b, W, kv, D), jnp.float32)
    v = jax.random.normal(keys[2], (3, b, W, kv, D), jnp.float32)
    valid = jnp.arange(W)[None, :] <= pos[:, None]  # (B, W)
    ref = grouped_masked_attention(
        q[:, None], k[layer], v[layer], valid[:, None, None]
    )[:, 0]
    # a walked block is read whole (its rows past the depth are masked),
    # the blocks after it and a parked slot's are never read
    blk = jnp.arange(W)[None, :] // block_w
    unread = (blk > pos[:, None] // block_w) | (pos[:, None] >= W)
    k, v = (jnp.where(unread[None, :, :, None, None], jnp.nan, x)
            for x in (k, v))
    if heads_major:
        k, v = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)
    out = decode_attention(
        q, k, v, jnp.int32(layer), pos, block_w=block_w,
        heads_major=heads_major,
    )
    live = np.asarray(pos < W)
    np.testing.assert_allclose(out[live], ref[live], atol=2e-6, rtol=0)
    assert not np.asarray(out[~live]).any()


def test_block_walk_names_each_live_slots_blocks_and_none_parked():
    """``pos // block_w + 1`` blocks a live slot, none a parked one; the
    blocks walked before each slot (so the buffer its first block lands
    in) and the live slot after it, whose first block follows its last."""
    pos = jnp.array([P, 0, 255, 256, P, P, 511, P], jnp.int32)
    count, first, after = (
        np.asarray(x).tolist() for x in block_walk(pos, W, BLOCK)
    )
    assert count == [0, 1, 1, 2, 0, 0, 2, 0]
    assert first == [0, 0, 1, 2, 4, 4, 4, 6]
    assert after == [1, 2, 3, 6, 6, 6, 8, 8]
    count, first, after = (
        np.asarray(x).tolist() for x in block_walk(jnp.full((3,), P), W, 128)
    )
    assert count == first == [0, 0, 0] and after == [3, 3, 3]


def test_dead_slots_ask_for_blocks_already_held():
    """A dead slot's index map names the block the slot before it ended on
    (the first live slot's first block where none is before it), so the
    pipeline fetches nothing new for it; all dead: one block in all."""
    pos = jnp.array([W, W, 10, W, 300, W], jnp.int32)
    src, hi = (np.asarray(x) for x in block_bounds(pos, W, BLOCK))
    assert src.tolist() == [2, 2, 2, 2, 4, 4]
    assert hi.tolist() == [0, 0, 0, 0, 1, 1]
    src, hi = (
        np.asarray(x) for x in block_bounds(jnp.full((3,), W), W, BLOCK)
    )
    assert src.tolist() == hi.tolist() == [0, 0, 0]


@pytest.mark.parametrize(
    "w, kv, d, dtype, rows",
    [
        (2048, 8, 128, jnp.bfloat16, 512),   # the chat cell
        (4096, 8, 128, jnp.bfloat16, 512),   # the long cell
        (2048, 8, 128, jnp.float32, 256),    # 1 MiB a block
        (2048, 16, 128, jnp.bfloat16, 256),
        (384, 2, 128, jnp.float32, 128),
        (64, 4, 128, jnp.float32, None),     # a window under a block
        (512, 2, 64, jnp.float32, None),     # half a lane tile
        (512, 64, 256, jnp.float32, None),   # no block under 1 MiB
    ],
)
def test_decode_block(w, kv, d, dtype, rows):
    assert decode_block(w, kv, d, dtype) == rows


def test_park_cache_index_moves_only_the_parked_rows_counters():
    cache = {
        "layers": {"attn": {
            "cache_index": jnp.array([[3, 7, 9], [3, 7, 9]], jnp.int32),
            "cached_key": jnp.ones((2, 3, 8, 1, 4)),
        }},
        "head": {"cache_index": jnp.array([1, 2, 3], jnp.int32)},
    }
    out = park_cache_index(cache, jnp.array([True, False, True]), 8)
    assert out["layers"]["attn"]["cache_index"].tolist() == [[8, 7, 8]] * 2
    assert out["head"]["cache_index"].tolist() == [8, 2, 8]
    assert out["layers"]["attn"]["cached_key"] is (
        cache["layers"]["attn"]["cached_key"]
    )


def _model(scan_layers, n_kv_heads=1, max_seq_len=256, **kw):
    cfg = TransformerConfig(
        vocab_size=64, d_model=256, n_layers=2, n_heads=2,
        n_kv_heads=n_kv_heads, d_ff=64, max_seq_len=max_seq_len,
        scan_layers=scan_layers, **kw,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    return model, params


def _prompt(seed, p_len):
    return jax.device_get(
        jax.random.randint(jax.random.PRNGKey(seed), (p_len,), 0, 64)
    ).tolist()


def _kernel_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("name=decode_attention")


@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["unrolled", "scanned"])
def test_model_decode_steps_run_the_kernel_and_match_the_full_forward(
    scan_layers,
):
    """``TransformerLM(decode=True)`` at ``head_dim`` 128, one position a
    call after a prefill: every step's logits equal the causal forward's at
    that position (which never decodes) to the kernel's tolerance, with the
    same greedy token, scanned (the stack at a traced layer) and unrolled
    (a layer's own variables as a stack of one)."""
    model, params = _model(scan_layers)
    tokens = jnp.asarray([_prompt(1, 12), _prompt(2, 12)], jnp.int32)
    full = model.apply({"params": params}, tokens)
    _, upd = model.apply(
        {"params": params}, tokens[:, :6], prefill=True, mutable=["cache"]
    )
    cache = upd["cache"]

    def step(cache, tok):
        return model.apply(
            {"params": params, "cache": cache}, tok, decode=True,
            mutable=["cache"],
        )

    assert _kernel_calls(step, cache, tokens[:, 6:7]) == (
        1 if scan_layers else 2
    )
    for t in range(6, 12):
        logits, upd = step(cache, tokens[:, t:t + 1])
        cache = upd["cache"]
        np.testing.assert_allclose(
            logits[:, 0], full[:, t], atol=2e-5, rtol=1e-5
        )
        assert (logits[:, 0].argmax(-1) == full[:, t].argmax(-1)).all()


def test_generate_through_the_kernel_matches_the_prefill_only_argmax():
    """``generate()`` sizes its window to the request (a multiple of 8): at
    120 + 8 positions that is one block of 128, and its decode steps (a
    scalar depth for all rows) take the kernel. Each new token is the
    argmax of the causal forward over the sequence so far."""
    model, params = _model(True, n_kv_heads=None)
    prompt = jnp.asarray([_prompt(3, 120), _prompt(4, 120)], jnp.int32)
    assert _kernel_calls(lambda p: generate(model, p, prompt, 8), params) == 1
    out = generate(model, params, prompt, 8)
    full = model.apply({"params": params}, out)
    assert (out[:, 120:] == full[:, 119:-1].argmax(-1)).all()
    # a window that is no whole block keeps the plain einsums
    assert _kernel_calls(
        lambda p: generate(model, p, prompt[:, :20], 8), params
    ) == 0


def test_a_chunk_of_positions_keeps_the_plain_path():
    """``s > 1`` (suffix prefill, chunked prefill, speculative verify) is
    not the kernel's: no call in the trace, and bitwise what it was."""
    model, params = _model(True)
    tokens = jnp.asarray([_prompt(5, 8)], jnp.int32)
    _, upd = model.apply(
        {"params": params}, tokens[:, :4], prefill=True, mutable=["cache"]
    )

    def chunk(cache, toks):
        return model.apply(
            {"params": params, "cache": cache}, toks, decode=True,
            mutable=["cache"],
        )

    assert _kernel_calls(chunk, upd["cache"], tokens[:, 4:8]) == 0
    assert _kernel_calls(chunk, upd["cache"], tokens[:, 4:5]) == 1
    # nor an int8 cache, at any chunk length
    qmodel, qparams = _model(True, kv_cache_dtype=jnp.int8)
    _, qupd = qmodel.apply(
        {"params": qparams}, tokens[:, :4], prefill=True, mutable=["cache"]
    )
    assert _kernel_calls(
        lambda c, t: qmodel.apply(
            {"params": qparams, "cache": c}, t, decode=True,
            mutable=["cache"],
        ), qupd["cache"], tokens[:, 4:5],
    ) == 0


def test_engine_serves_through_the_kernel_token_exact_to_generate():
    """4 slots at ``head_dim`` 128 (2 query heads on 1 KV head), 7
    requests of mixed lengths arriving while others decode: slots fall
    empty mid-chain, wait parked at depth W, and are refilled at the new
    request's depth. Greedy tokens equal ``generate()``'s a request. (A
    float32 cache: a bfloat16 one rounds K and V where engine and
    ``generate()`` differ in the last bit, and a random model's near-ties
    then flip on either path.)"""
    model, params = _model(True)
    engine = ServeEngine(model, params, n_slots=4, tokens_per_launch=4)
    reqs = [(3, 9), (7, 3), (5, 5), (12, 6), (2, 11), (9, 1), (4, 7)]
    prompts = [_prompt(100 + i, p) for i, (p, _) in enumerate(reqs)]
    ids = {}
    for i in range(3):  # one slot starts empty
        ids[i] = engine.submit(
            Request(prompt=prompts[i], max_new_tokens=reqs[i][1])
        )
    pending = list(range(3, len(reqs)))
    done = {}
    rounds = 0
    while not engine.idle or pending:
        rounds += 1
        if pending and rounds % 2 == 0:  # slots stay empty a chain or two
            i = pending.pop(0)
            ids[i] = engine.submit(
                Request(prompt=prompts[i], max_new_tokens=reqs[i][1])
            )
        for c in engine.step():
            done[c.request_id] = c
    state = engine._state
    assert int(state["remaining"].max()) == 0
    for i, (_, max_new) in enumerate(reqs):
        ref = generate(
            model, params, jnp.asarray([prompts[i]], jnp.int32), max_new
        )
        ref = jax.device_get(ref)[0, len(prompts[i]):].tolist()
        assert done[ids[i]].tokens == ref, i
        assert done[ids[i]].finish_reason == "length"
    # the chain ran the kernel, under its scope
    text = str(jax.make_jaxpr(engine._chain_fn)(params, state))
    assert text.count("name=decode_attention") == 1

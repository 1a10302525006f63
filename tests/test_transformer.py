"""Transformer LM: shapes, causality, scan/loop equivalence, learnability."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_training_tutorials_tpu.data import (
    ShardedLoader,
    synthetic_lm,
)
from pytorch_distributed_training_tutorials_tpu.models import (
    TransformerConfig,
    TransformerLM,
    stack_quantized_lm_params,
)
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
from pytorch_distributed_training_tutorials_tpu.train import Trainer

CFG = TransformerConfig(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                        max_seq_len=32)


def _init_and_apply(cfg, tokens, seed=0):
    model = TransformerLM(cfg)
    variables = model.init(jax.random.PRNGKey(seed), tokens)
    return model, variables, model.apply(variables, tokens)


def test_forward_shape_and_dtype():
    tokens = jnp.zeros((2, 16), jnp.int32)
    _, _, logits = _init_and_apply(CFG, tokens)
    assert logits.shape == (2, 16, 64)
    assert logits.dtype == jnp.float32


def test_causality():
    """Logits at position t must not depend on tokens after t."""
    rng = np.random.Generator(np.random.PCG64(0))
    tokens = rng.integers(0, 64, (1, 16)).astype(np.int32)
    model, variables, logits = _init_and_apply(CFG, jnp.asarray(tokens))
    perturbed = tokens.copy()
    perturbed[0, 10:] = (perturbed[0, 10:] + 7) % 64
    logits_p = model.apply(variables, jnp.asarray(perturbed))
    np.testing.assert_allclose(
        np.asarray(logits[0, :10]), np.asarray(logits_p[0, :10]), atol=1e-5
    )
    assert not np.allclose(np.asarray(logits[0, 10:]), np.asarray(logits_p[0, 10:]))


def test_scan_matches_loop():
    """scan_layers=True is a compile-time optimization, not a model change —
    same params (transposed into the stacked layout) give the same logits."""
    tokens = jnp.asarray(
        np.random.Generator(np.random.PCG64(1)).integers(0, 64, (2, 8)),
        jnp.int32,
    )
    loop_cfg = CFG
    scan_cfg = TransformerConfig(**{**CFG.__dict__, "scan_layers": True})
    _, loop_vars, loop_logits = _init_and_apply(loop_cfg, tokens)

    # restack loop params [block_0, block_1] -> scanned layout
    blocks = [loop_vars["params"][f"block_{i}"] for i in range(CFG.n_layers)]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *blocks
    )
    scan_params = {
        "tok_emb": loop_vars["params"]["tok_emb"],
        "final_norm": loop_vars["params"]["final_norm"],
        "lm_head": loop_vars["params"]["lm_head"],
        "layers": {"block": stacked},
    }
    scan_logits = TransformerLM(scan_cfg).apply({"params": scan_params}, tokens)
    np.testing.assert_allclose(
        np.asarray(loop_logits), np.asarray(scan_logits), atol=1e-5
    )


def test_remat_matches_plain():
    tokens = jnp.zeros((2, 8), jnp.int32)
    remat_cfg = TransformerConfig(**{**CFG.__dict__, "remat": True})
    _, variables, plain = _init_and_apply(CFG, tokens)
    remat_logits = TransformerLM(remat_cfg).apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(remat_logits), atol=1e-6)


@pytest.mark.parametrize(
    "variant",
    ["plain", "gqa", "scan"],
)
def test_chunked_decode_matches_full_prefill(variant):
    """Suffix prefill (decode with S>1 from a nonzero cache offset) is the
    SAME math as one batched prefill: prefill [0, d), then decode the
    bucket-padded suffix [d, P) in one chunk, and the next-token logits,
    and every cache row in [0, P), must equal the full prefill's (to the
    last float32 digit: XLA:CPU fuses the two programs differently, so a
    logit may differ in its final ulp — float tolerance, not bitwise). This is the exactness contract the serve/ prefix cache
    leans on (splice a retained segment, prefill only the suffix)."""
    overrides = {
        "plain": {},
        "gqa": {"n_kv_heads": 2},
        "scan": {"scan_layers": True},
    }[variant]
    cfg = TransformerConfig(**{**CFG.__dict__, "max_seq_len": 64, **overrides})
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))[
        "params"
    ]
    P, d, pad_to = 13, 5, 16  # suffix 8 real tokens padded to a pow2 bucket
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, P), 0, cfg.vocab_size)

    full, upd_full = model.apply(
        {"params": params}, tokens, prefill=True, mutable=["cache"],
        last_pos=P - 1,
    )

    _, upd = model.apply(
        {"params": params}, tokens[:, :d], prefill=True, mutable=["cache"],
        last_pos=d - 1,
    )
    suffix = jnp.concatenate(
        [tokens[:, d:], jnp.zeros((1, pad_to - (P - d)), jnp.int32)], axis=1
    )
    chunk, upd_chunk = model.apply(
        {"params": params, "cache": upd["cache"]}, suffix, decode=True,
        mutable=["cache"], last_pos=P - 1 - d,
    )

    np.testing.assert_allclose(
        np.asarray(full[:, -1]), np.asarray(chunk[:, -1]),
        rtol=1e-5, atol=1e-6,
    )
    seq_axis = 2 if cfg.scan_layers else 1
    for a, b in zip(
        jax.tree_util.tree_leaves(upd_full["cache"]),
        jax.tree_util.tree_leaves(upd_chunk["cache"]),
    ):
        if a.ndim <= seq_axis:
            continue  # cache_index scalars
        sl = [slice(None)] * a.ndim
        sl[seq_axis] = slice(0, P)
        np.testing.assert_allclose(
            np.asarray(a[tuple(sl)]), np.asarray(b[tuple(sl)]),
            rtol=1e-5, atol=1e-6,
        )


def test_chunked_decode_int8_kv_argmax_only():
    """With a reduced-precision cache the suffix chunk attends over the
    ROUNDED stored K/V while full prefill attends over the unrounded local
    values (the CLAUDE.md kv_cache_dtype caveat), so bit-exactness is not
    pinned — only the greedy choice is, on this easy-margin tiny model."""
    cfg = TransformerConfig(
        **{**CFG.__dict__, "max_seq_len": 64, "kv_cache_dtype": jnp.int8}
    )
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))[
        "params"
    ]
    P, d = 13, 5
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, P), 0, cfg.vocab_size)
    full, _ = model.apply(
        {"params": params}, tokens, prefill=True, mutable=["cache"],
        last_pos=P - 1,
    )
    _, upd = model.apply(
        {"params": params}, tokens[:, :d], prefill=True, mutable=["cache"],
        last_pos=d - 1,
    )
    suffix = jnp.concatenate([tokens[:, d:], jnp.zeros((1, 8), jnp.int32)], 1)
    chunk, _ = model.apply(
        {"params": params, "cache": upd["cache"]}, suffix, decode=True,
        mutable=["cache"], last_pos=P - 1 - d,
    )
    assert np.array_equal(
        np.asarray(full[:, -1]).argmax(-1), np.asarray(chunk[:, -1]).argmax(-1)
    )


# --- the layer scan carries a cache it was handed (ISSUE 28) ---------------
# scan_layers=True with a cache in the apply carries the stacked tree through
# the scan and each layer writes/reads it at [layer]; the oracle is the
# unrolled model, layer by layer, on the same cache unstacked.

CARRY_BASE = dict(vocab_size=64, d_model=64, n_layers=3, n_heads=4,
                  n_kv_heads=2, max_seq_len=16)
CARRY_CASES = {
    # name: (config overrides, cache_index: per-row depths | scalar, tokens
    #        a step, steps, apply kind)
    "bf16_unequal_depths": (dict(kv_cache_dtype=jnp.bfloat16), (3, 9), 1, 2,
                            "decode"),
    "f32_mha": (dict(n_kv_heads=None), (0, 5), 1, 2, "decode"),
    "gqa_16_8": (dict(n_heads=16, n_kv_heads=8), (2, 7), 1, 2, "decode"),
    "scalar_position": (dict(), 4, 1, 3, "decode"),
    "scalar_position_chunk": (dict(), 4, 4, 1, "decode"),
    # row 1 writes positions 12..19 of a 16-position window: 16..19 drop
    "chunk_past_the_window": (dict(kv_cache_dtype=jnp.bfloat16), (3, 12), 8,
                              1, "decode"),
    "int8_kv": (dict(kv_cache_dtype=jnp.int8), (3, 9), 1, 2, "decode"),
    "int4_kv": (dict(kv_cache_dtype="int4"), (3, 9), 1, 2, "decode"),
    "int8_kv_chunk": (dict(kv_cache_dtype=jnp.int8), (1, 11), 8, 1,
                      "decode"),
    "paged_gather": (dict(kv_pages=8, kv_page_size=4), (3, 9), 1, 2,
                     "decode"),
    "paged_gather_int8_chunk": (
        dict(kv_pages=8, kv_page_size=4, kv_cache_dtype=jnp.int8), (2, 13),
        4, 1, "decode"),
    "paged_kernel": (dict(kv_pages=8, kv_page_size=4, paged_kernel=True),
                     (3, 9), 1, 1, "decode"),
    "lora": (dict(lora_adapters=3, lora_rank=2), (3, 9), 1, 2, "decode"),
    "remat": (dict(remat=True), (3, 9), 1, 1, "decode"),
    "prefill_into_a_cache": (dict(kv_cache_dtype=jnp.bfloat16), 0, 6, 1,
                             "prefill"),
}


def _carry_models(overrides):
    """The unrolled model, its scanned twin, and one set of random params in
    both layouts (LoRA factors init to zero: every leaf gets noise)."""
    base = {**CARRY_BASE, **overrides}
    loop = TransformerLM(TransformerConfig(**base))
    scan = TransformerLM(TransformerConfig(**base, scan_layers=True))
    params = loop.init(jax.random.PRNGKey(0), jnp.zeros((2, 4), jnp.int32))[
        "params"
    ]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)
    ])
    # stacks any tree of block_<i> subtrees (params here, the cache below)
    return loop, scan, params, stack_quantized_lm_params(params)


def _start_cache(loop, depths, rng):
    """A decode cache of the unrolled model with content: random K/V (and
    scales), ``cache_index`` at ``depths``, and for a paged model a page
    table that backs each row's first pages only (the rest keep the
    sentinel, so writes there drop)."""
    cfg = loop.cfg
    cache = loop.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32), decode=True
    )["cache"]

    def fill(path, leaf):
        name = path[-1].key
        if name == "cache_index":
            return jnp.broadcast_to(jnp.asarray(depths, jnp.int32),
                                    () if np.ndim(depths) == 0 else (2,))
        if name == "page_table":
            table = np.full(leaf.shape, cfg.kv_pages, np.int32)
            table[0, :2] = [5, 1]
            table[1, :3] = [0, 7, 2]
            return jnp.asarray(table)
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            return jnp.asarray(
                rng.integers(0, 127, leaf.shape), leaf.dtype)
        return jnp.asarray(
            rng.uniform(0.01, 1.0, leaf.shape), leaf.dtype)

    return jax.tree_util.tree_map_with_path(fill, cache)


@pytest.mark.parametrize("case", sorted(CARRY_CASES))
def test_carried_cache_matches_unrolled_layers(case):
    """Decode (or prefill) into a cache the apply was handed: the scanned
    model, which carries the stacked cache, gives the unrolled model's
    logits and leaves the unrolled model's cache, leaf for leaf."""
    overrides, depths, s, steps, kind = CARRY_CASES[case]
    loop, scan, params, stacked = _carry_models(overrides)
    rng = np.random.Generator(np.random.PCG64(7))
    cache = _start_cache(loop, depths, rng)
    scan_cache = stack_quantized_lm_params(cache)
    kw = {"prefill": True} if kind == "prefill" else {"decode": True}
    if loop.cfg.lora_adapters:
        kw["adapter_ids"] = jnp.asarray([2, 0], jnp.int32)
    for _ in range(steps):
        tokens = jnp.asarray(rng.integers(0, 64, (2, s)), jnp.int32)
        want, upd = loop.apply(
            {"params": params, "cache": cache}, tokens, mutable=["cache"],
            **kw)
        got, scan_upd = scan.apply(
            {"params": stacked, "cache": scan_cache}, tokens,
            mutable=["cache"], **kw)
        cache, scan_cache = upd["cache"], scan_upd["cache"]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
        want_cache = stack_quantized_lm_params(cache)
        assert (jax.tree_util.tree_structure(scan_cache)
                == jax.tree_util.tree_structure(want_cache))
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(scan_cache),
            jax.tree_util.tree_leaves(want_cache),
        ):
            assert a.shape == b.shape and a.dtype == b.dtype, path
            if jnp.issubdtype(a.dtype, jnp.integer):
                # positions, tables, int8 / packed int4 K/V: the same
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b), err_msg=str(path))
            else:
                # float K/V and scales: the two programs fuse differently,
                # so a value may differ in its last digits (one step of a
                # bfloat16 leaf where the rounding flips)
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=max(1e-4, 2 * float(jnp.finfo(a.dtype).eps)),
                    atol=1e-5, err_msg=str(path))


@pytest.mark.parametrize("storage", [
    dict(), dict(kv_cache_dtype=jnp.bfloat16), dict(kv_cache_dtype=jnp.int8),
    dict(kv_cache_dtype="int4"), dict(kv_pages=8, kv_page_size=4),
    dict(kv_pages=8, kv_page_size=4, kv_cache_dtype=jnp.int8),
], ids=["f32", "bf16", "int8", "int4", "paged", "paged_int8"])
def test_creating_and_carrying_applies_return_one_tree(storage):
    """An apply that creates its cache scans over it; one that was handed
    that cache carries it. Paths, shapes and dtypes are the same, so what
    walks the tree (serve/slots.py, SLOT_STATE_RULES, rewind_cache_index)
    cannot tell which scan ran."""
    _, scan, _, stacked = _carry_models(storage)
    tokens = jnp.zeros((2, 1), jnp.int32)

    def created(p):
        return scan.apply(
            {"params": p}, tokens, decode=True, mutable=["cache"]
        )[1]["cache"]

    def carried(p):
        return scan.apply(
            {"params": p, "cache": created(p)}, tokens, decode=True,
            mutable=["cache"],
        )[1]["cache"]

    def shapes(tree):
        return {
            jax.tree_util.keystr(k): (v.shape, str(v.dtype))
            for k, v in jax.tree_util.tree_leaves_with_path(tree)
        }

    first, second = jax.eval_shape(created, stacked), jax.eval_shape(
        carried, stacked)
    assert shapes(first) == shapes(second)
    assert all(k.startswith("['layers']['block']['attn']")
               for k in shapes(first))
    assert all(v[0][0] == CARRY_BASE["n_layers"]
               for v in shapes(first).values())


def test_the_scan_carries_a_cache_only_when_handed_one():
    """The jaxpr of a carrying apply has the stacked K/V among the layer
    scan's carries (no stacked K/V output); a creating apply and a training
    forward have none."""
    _, scan, _, stacked = _carry_models(dict())
    n = scan.cfg.n_layers
    tokens = jnp.zeros((2, 1), jnp.int32)
    cache = jax.eval_shape(
        lambda p: scan.apply(
            {"params": p}, tokens, decode=True, mutable=["cache"]
        )[1]["cache"], stacked)
    kv_shape = cache["layers"]["block"]["attn"]["cached_key"].shape

    def layer_scan(fn, *args):
        eqns = [e for e in jax.make_jaxpr(fn)(*args).eqns
                if e.primitive.name == "scan" and e.params["length"] == n]
        assert len(eqns) == 1
        return eqns[0]

    def stacked_kv(avals):
        return [v.aval for v in avals if v.aval.shape == kv_shape]

    carrying = layer_scan(
        lambda p, c: scan.apply(
            {"params": p, "cache": c}, tokens, decode=True,
            mutable=["cache"]),
        stacked, cache)
    n_carry = carrying.params["num_carry"]
    assert len(stacked_kv(carrying.outvars[:n_carry])) == 2  # K and V
    assert not stacked_kv(carrying.outvars[n_carry:])
    creating = layer_scan(
        lambda p: scan.apply(
            {"params": p}, tokens, decode=True, mutable=["cache"]),
        stacked)
    n_carry = creating.params["num_carry"]
    assert not stacked_kv(creating.outvars[:n_carry])
    assert len(stacked_kv(creating.outvars[n_carry:])) == 2
    training = layer_scan(
        lambda p: scan.apply({"params": p}, jnp.zeros((2, 8), jnp.int32)),
        stacked)
    assert training.params["num_carry"] == 1  # the activations alone


@pytest.mark.slow
def test_lm_loss_decreases_data_parallel():
    """End-to-end: the bigram dataset is learnable; CE drops well below
    log(vocab) (uniform-prediction level) within a few epochs."""
    mesh = create_mesh({"data": 8})
    ds = synthetic_lm(size=512, seq_len=32, vocab_size=64)
    loader = ShardedLoader(ds, 8, mesh)
    trainer = Trainer(
        TransformerLM(CFG), loader, optax.adam(3e-3), loss="cross_entropy"
    )
    first = trainer._run_epoch(0)
    last = trainer.train(4)
    assert first["loss"] < np.log(64) + 0.5
    assert last["loss"] < first["loss"] * 0.75

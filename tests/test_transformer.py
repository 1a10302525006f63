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

"""Pallas flash attention: equivalence with dense causal attention.

The kernel must be a drop-in ``attention_fn`` — same math as
``causal_attention`` (reference has no attention of its own; SURVEY.md
section 5.7), different memory story. Interpreter mode runs the identical
kernel code path on the CPU mesh (what the kernel reaches on the chip is
``flash_attention_roofline`` in the benchmark's training cell).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    causal_attention,
)
from pytorch_distributed_training_tutorials_tpu.ops.flash_attention import (
    flash_attention,
    make_flash_attention,
)

from helpers import requires_pallas_interpret

# every test here executes the Pallas kernel in Mosaic-interpret mode
pytestmark = requires_pallas_interpret


def _qkv(b, s, h, d, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(
        jax.random.normal(k, (b, s, h, d), dtype) for k in keys
    )


@pytest.mark.parametrize(
    "b,s,h,d,bq,bk",
    [
        (2, 256, 4, 64, 128, 128),  # multi-block, block-divisible
        (1, 200, 2, 32, 128, 128),  # multi-block WITH padded tail (n_k=2,
        #                             pad=56): padded keys must stay masked
        (1, 200, 2, 32, 512, 512),  # same length, single clamped block
        (2, 64, 2, 16, 512, 512),   # block clamps to the (8-aligned) seq
    ],
)
def test_forward_matches_dense(b, s, h, d, bq, bk):
    q, k, v = _qkv(b, s, h, d)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, bq, bk)),
        np.asarray(causal_attention(q, k, v)),
        atol=2e-5,
        rtol=2e-5,
    )


def test_unequal_block_sizes():
    q, k, v = _qkv(1, 192, 2, 32)
    out = flash_attention(q, k, v, 64, 128)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(causal_attention(q, k, v)),
        atol=2e-5, rtol=2e-5,
    )


def test_gradients_match_dense():
    q, k, v = _qkv(2, 256, 2, 32, seed=3)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * g)

    dense = jax.grad(loss(causal_attention), argnums=(0, 1, 2))(q, k, v)
    flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", dense, flash):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5,
            err_msg=f"d{name}",
        )


def test_gradients_match_dense_padded():
    """The padded-tail rows must not leak into real gradients (their lse is
    -inf; the kernels guard the exp shift). Block 64 forces a true
    multi-block padded layout (n_q = n_k = 2, pad = 28)."""
    q, k, v = _qkv(1, 100, 2, 16, seed=4)
    g = jax.random.normal(jax.random.PRNGKey(5), q.shape)
    dense = jax.grad(
        lambda *a: jnp.sum(causal_attention(*a) * g), argnums=(0, 1, 2)
    )(q, k, v)
    flash = jax.grad(
        lambda *a: jnp.sum(flash_attention(*a, 64, 64) * g),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(dense, flash):
        assert np.isfinite(np.asarray(b)).all()
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5
        )


def test_bfloat16_tolerance():
    q, k, v = _qkv(1, 256, 2, 64, dtype=jnp.bfloat16, seed=7)
    out = flash_attention(q, k, v)
    ref = causal_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=0.05, rtol=0.05,
    )


def test_as_attention_fn_trains():
    """flash_attention slots into TransformerConfig.attention_fn: logits
    match the dense model exactly in structure and a train step produces
    finite grads."""
    cfg_kw = dict(
        vocab_size=64, d_model=64, n_layers=2, n_heads=4, max_seq_len=128
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (2, 128), 0, 64, jnp.int32
    )
    dense_model = TransformerLM(TransformerConfig(**cfg_kw))
    flash_model = TransformerLM(
        TransformerConfig(attention_fn=make_flash_attention(64, 64), **cfg_kw)
    )
    params = dense_model.init(jax.random.PRNGKey(1), tokens)
    ref = dense_model.apply(params, tokens)
    out = flash_model.apply(params, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )

    def loss_fn(p):
        logits = flash_model.apply(p, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]
        ).mean()

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    assert any(float(jnp.abs(g).max()) > 0 for g in flat)

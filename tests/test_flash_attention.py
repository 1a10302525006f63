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
    grouped_masked_attention,
)
from pytorch_distributed_training_tutorials_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_forward,
    make_flash_attention,
    prefill_takes_kernel,
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


# (s, block): a bucket of whole blocks (64, 512, 2048), shorter than one
# block (40: the block clamps to the 8-aligned length), no whole number of
# blocks (300 in blocks of 128: a padded tail)
_GROUPED_LENGTHS = [(64, 32), (512, 128), (2048, 512), (40, 128), (300, 128)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("h,kv", [(4, 2), (32, 8), (20, 4)])
@pytest.mark.parametrize("s,block", _GROUPED_LENGTHS)
def test_grouped_forward_matches_grouped_dense(s, block, h, kv, dtype):
    """K and V at their stored head count (``KV < H``): each query head
    attends over its group's rows, as ``grouped_masked_attention`` under the
    causal mask does, with no copy of K and V to ``H`` heads. Two sequences
    a call (one at 2,048, for the suite's time), so that a batch row's
    heads take their own row's K and V."""
    b, d = (1, 16) if s > 512 else (2, 32)
    keys = jax.random.split(jax.random.PRNGKey(s + h), 3)
    q = jax.random.normal(keys[0], (b, s, h, d), dtype)
    k = jax.random.normal(keys[1], (b, s, kv, d), dtype)
    v = jax.random.normal(keys[2], (b, s, kv, d), dtype)
    out = flash_attention_forward(q, k, v, block, block)
    assert out.shape == q.shape and out.dtype == dtype
    mask = jnp.tril(jnp.ones((s, s), jnp.bool_))[None, None]
    ref = grouped_masked_attention(q, k, v, mask)
    tol = 2e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


def test_forward_alone_equals_the_differentiable_call_at_equal_heads():
    """``H == KV``: the forward alone is ``flash_attention``'s own forward,
    bit for bit (one kernel; the old index map)."""
    q, k, v = _qkv(2, 192, 2, 32, seed=11)
    assert (
        flash_attention_forward(q, k, v, 64, 64)
        == flash_attention(q, k, v, 64, 64)
    ).all()
    with pytest.raises(ValueError, match="no multiple"):
        flash_attention_forward(q, jnp.repeat(k[:, :, :1], 3, axis=2), v)


@pytest.mark.parametrize("s,d,takes", [
    (4096, 128, True), (1024, 128, True), (2048, 256, True),
    (4096, 64, False), (4096, 192, False), (8, 128, False), (512, 128, False),
])
def test_prefill_takes_kernel(s, d, takes):
    """Whole lane tiles and a length from the measured threshold up."""
    assert prefill_takes_kernel(s, d) is takes


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

"""A prefill's causal attention through the flash forward kernel, picked by
what ``Attention`` can see (ISSUE 37): the model's own attention, no mesh, a
head width of whole lane tiles, a length from the measured threshold up.
Both forms on one small model with ``head_dim`` 128: the cache they write is
the same array, the logits agree to float tolerance, greedy decoding gives
the same tokens; and every refusal of the rule keeps the dense form, which
toy head widths (the rest of the suite) take by the rule itself."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from pytorch_distributed_training_tutorials_tpu.models import generate
from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    causal_attention,
)

from helpers import requires_pallas_interpret

pytestmark = requires_pallas_interpret

flash = importlib.import_module(
    "pytorch_distributed_training_tutorials_tpu.ops.flash_attention"
)
S = 1024  # the bucket at the threshold


def _model(**kw):
    kw = dict(
        vocab_size=64, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=2 * S, scan_layers=True,
    ) | kw
    model = TransformerLM(TransformerConfig(**kw))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    return model, params


def _tokens(seed, n, rows=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, n), 0, 64)


def _prefill(model, params, tokens):
    return model.apply(
        {"params": params}, tokens, prefill=True, mutable=["cache"]
    )


def _kernel_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("name=flash_attention_fwd")


def test_threshold_is_a_bucket():
    assert flash.prefill_takes_kernel(S, 128)
    assert not flash.prefill_takes_kernel(S // 2, 128)


@pytest.mark.parametrize("kv_heads,cache_dtype", [
    (1, None), (2, jnp.bfloat16), (1, jnp.int8),
], ids=["gqa-float32", "mha-bfloat16", "gqa-int8"])
def test_both_forms_write_one_cache_and_agree(kv_heads, cache_dtype,
                                               monkeypatch):
    """The cache's storage is no condition of the rule (a prefill attends
    over the raw K and V; the cache is written before attention either way):
    bit-equal K, V and counters on both forms, logits to float tolerance."""
    model, params = _model(n_kv_heads=kv_heads, kv_cache_dtype=cache_dtype)
    tokens = _tokens(1, S, rows=2)
    assert _kernel_calls(lambda t: _prefill(model, params, t), tokens) == 1
    logits, upd = _prefill(model, params, tokens)
    monkeypatch.setattr(flash, "prefill_takes_kernel", lambda s, d: False)
    assert _kernel_calls(lambda t: _prefill(model, params, t), tokens) == 0
    dense_logits, dense_upd = _prefill(model, params, tokens)
    # layer 0's K and V come before any attention: bit-equal; layer 1's are
    # projections of an input that differs by the kernel's tolerance
    flat = jax.tree_util.tree_leaves_with_path(upd["cache"])
    dense_flat = jax.tree_util.tree_leaves(dense_upd["cache"])
    assert len(flat) == len(dense_flat)
    for (path, a), b in zip(flat, dense_flat):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert (a[0] == b[0]).all(), path
        if a.dtype == jnp.int8:
            assert int(jnp.abs(a.astype(jnp.int32) - b).max()) <= 1, path
        else:
            # a bfloat16 row of layer 1 may round the other way: one ulp
            tol = 1e-2 if a.dtype == jnp.bfloat16 else 2e-5
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=tol, rtol=tol, err_msg=str(path),
            )
    np.testing.assert_allclose(logits, dense_logits, atol=2e-5, rtol=1e-5)
    # and the causal forward without a cache (training's path) is the same
    full = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(logits, full[:, -1:], atol=2e-5, rtol=1e-5)


def test_greedy_tokens_are_the_same_on_both_forms(monkeypatch):
    model, params = _model()
    prompt = _tokens(2, S, rows=2)
    assert _kernel_calls(lambda p: generate(model, p, prompt, 6), params) == 1
    out = generate(model, params, prompt, 6)
    monkeypatch.setattr(flash, "prefill_takes_kernel", lambda s, d: False)
    jax.clear_caches()  # generate() keeps its traced programs
    assert _kernel_calls(lambda p: generate(model, p, prompt, 6), params) == 0
    assert (generate(model, params, prompt, 6) == out).all()


@pytest.mark.parametrize("why,kw,n,mode", [
    ("a mesh", dict(tp_mesh=True), S, "prefill"),
    ("a head width off the lane tile", dict(d_model=128), S, "prefill"),
    ("a stated head width off the lane tile", dict(d_head=64), S, "prefill"),
    ("a user's attention_fn", dict(attention_fn=causal_attention), S,
     "prefill"),
    ("a length under the threshold", {}, S // 2, "prefill"),
    ("training", {}, S, "train"),
    ("a chunked continuation", {}, S, "chunk"),
])
def test_each_refusal_keeps_the_dense_form(why, kw, n, mode):
    if kw.get("tp_mesh"):  # no device is touched while the file is imported
        kw = dict(tp_mesh=Mesh(np.array(jax.devices()[:1]), ("model",)))
    model, params = _model(**kw)
    tokens = _tokens(3, n)
    if mode == "train":
        fn = lambda t: model.apply({"params": params}, t)  # noqa: E731
    elif mode == "prefill":
        fn = lambda t: _prefill(model, params, t)  # noqa: E731
    else:
        _, upd = _prefill(model, params, tokens[:, :8])
        fn = lambda t: model.apply(  # noqa: E731
            {"params": params, "cache": upd["cache"]}, t, decode=True,
            mutable=["cache"],
        )
    assert _kernel_calls(fn, tokens) == 0, why
    if mode == "prefill":
        # the scope is on the dense form too: the trace says which ran
        text = jax.jit(fn).lower(tokens).as_text(debug_info=True)
        assert "attn/prefill_attn/" in text

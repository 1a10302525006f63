"""Fused logits-free cross entropy: equivalence with the optax path.

The kernel must be a drop-in for
``optax.softmax_cross_entropy_with_integer_labels(hidden @ lm_head, y)``
(the reference loss, ``ddp_gpus.py:37``) with a different memory story:
no (B, S, V) logits tensor, blockwise forward/backward (interpreter mode
runs the identical kernel code path on the CPU mesh). The headline
receipt — the compiled 350m-config train step contains NO live
[B, S, V]-shaped float intermediate while the baseline provably does —
is pinned here by HLO inspection.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from pytorch_distributed_training_tutorials_tpu.models import (
    TransformerConfig,
    TransformerLM,
)
from pytorch_distributed_training_tutorials_tpu.ops.fused_loss import (
    fused_cross_entropy,
    fused_cross_entropy_reference,
    fused_cross_entropy_tp,
)
from pytorch_distributed_training_tutorials_tpu.train.trainer import (
    TrainState,
    make_train_step,
)

from helpers import requires_pallas_interpret

pytestmark = requires_pallas_interpret


def _hwy(b, s, d, v, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(keys[0], (b, s, d))
    w = jax.random.normal(keys[1], (d, v)) * (d ** -0.5)
    y = jax.random.randint(keys[2], (b, s), 0, v)
    return h, w, y


def _optax_loss(h, w, y):
    logits = jnp.einsum(
        "bsd,dv->bsv", h, w, preferred_element_type=jnp.float32
    )
    return optax.softmax_cross_entropy_with_integer_labels(logits, y)


@pytest.mark.parametrize(
    "b,s,d,v,bn,bv",
    [
        (2, 32, 16, 64, 16, 16),   # multi-block, block-divisible
        (1, 24, 32, 50, 16, 16),   # padded tail rows AND vocab columns
        (1, 24, 32, 50, 512, 512),  # single clamped block
        (2, 8, 8, 9, 8, 8),        # tiny, vocab pad = 7 of 16
    ],
)
def test_forward_matches_optax(b, s, d, v, bn, bv):
    h, w, y = _hwy(b, s, d, v)
    out = fused_cross_entropy(h, w, y, block_n=bn, block_v=bv)
    ref = _optax_loss(h, w, y)
    assert out.shape == y.shape
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(fused_cross_entropy_reference(h, w, y)),
        np.asarray(ref), atol=2e-5, rtol=2e-5,
    )


def test_gradients_match_optax():
    h, w, y = _hwy(2, 24, 32, 50, seed=3)

    def mean_loss(fn):
        return lambda h, w: fn(h, w).mean()

    dense = jax.grad(
        mean_loss(lambda h, w: _optax_loss(h, w, y)), argnums=(0, 1)
    )(h, w)
    fused = jax.grad(
        mean_loss(
            lambda h, w: fused_cross_entropy(h, w, y, block_n=16, block_v=16)
        ),
        argnums=(0, 1),
    )(h, w)
    for name, a, b in zip(("dh", "dw"), dense, fused):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-6, rtol=5e-5,
            err_msg=name,
        )


def test_weighted_per_token_losses_match():
    """Per-token output contract: a row-validity mask (the wrap-padded
    duplicate rows ShardedLoader.valid_mask identifies) weights the fused
    losses exactly like the optax ones — masked means agree."""
    h, w, y = _hwy(4, 16, 16, 32, seed=5)
    mask = jnp.asarray([1.0, 1.0, 1.0, 0.0])[:, None]  # last row = wrap pad
    out = fused_cross_entropy(h, w, y, block_n=16, block_v=16)
    ref = _optax_loss(h, w, y)
    got = (out * mask).sum() / mask.sum() / y.shape[1]
    want = (ref * mask).sum() / mask.sum() / y.shape[1]
    np.testing.assert_allclose(float(got), float(want), atol=2e-6, rtol=2e-6)


def test_bfloat16_tolerance():
    h, w, y = _hwy(1, 32, 32, 64, seed=7)
    hb, wb = h.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    out = fused_cross_entropy(hb, wb, y, block_n=16, block_v=16)
    ref = _optax_loss(hb, wb, y)  # f32-accumulated, like the kernel
    assert out.dtype == jnp.float32  # losses stay f32 regardless of input
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=0.05, rtol=0.05
    )


def test_tp_vocab_sharded_matches(devices):
    """The shard_map variant over a dp x tp mesh: vocab-split head,
    axis-reduced logsumexp — loss AND grads match the unsharded op."""
    mesh = Mesh(np.array(devices).reshape(2, 4), ("data", "model"))
    h, w, y = _hwy(2, 24, 32, 48, seed=9)  # V=48 -> 12 columns per shard

    out = fused_cross_entropy_tp(h, w, y, mesh, block_n=16, block_v=8)
    ref = _optax_loss(h, w, y)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )

    def mean_loss(fn):
        return lambda h, w: fn(h, w).mean()

    dense = jax.grad(
        mean_loss(lambda h, w: _optax_loss(h, w, y)), argnums=(0, 1)
    )(h, w)
    fused = jax.grad(
        mean_loss(
            lambda h, w: fused_cross_entropy_tp(
                h, w, y, mesh, block_n=16, block_v=8
            )
        ),
        argnums=(0, 1),
    )(h, w)
    for name, a, b in zip(("dh", "dw"), dense, fused):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-6, rtol=5e-5,
            err_msg=name,
        )


def test_tp_validates():
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("model",))
    h, w, y = _hwy(1, 8, 8, 9)
    with pytest.raises(ValueError, match="not divisible"):
        fused_cross_entropy_tp(h, w, y, mesh)  # 9 % 8 != 0
    with pytest.raises(ValueError, match="no 'tp' axis"):
        fused_cross_entropy_tp(h, w, y, mesh, axis="tp")


def test_train_step_fused_matches_baseline():
    """make_train_step(loss="fused_cross_entropy"): same loss and same
    post-step params as the standard logits path, via return_hidden."""
    import optax as _optax

    cfg = TransformerConfig(
        vocab_size=37, d_model=32, n_layers=2, n_heads=4, max_seq_len=32
    )
    model = TransformerLM(cfg)
    toks = jax.random.randint(
        jax.random.PRNGKey(0), (2, 17), 0, 37, jnp.int32
    )
    batch = (toks[:, :-1], toks[:, 1:])
    params = model.init(jax.random.PRNGKey(1), batch[0])["params"]

    def run(loss):
        # private param buffers: the jitted step donates its state
        p = jax.tree_util.tree_map(jnp.array, params)
        state = TrainState.create(
            apply_fn=model.apply, params=p,
            tx=_optax.adamw(1e-3, weight_decay=0.01),
        )
        step = make_train_step(loss=loss)
        state, metrics = step(state, batch)
        return state, float(metrics["loss"])

    st_base, loss_base = run("cross_entropy")
    st_fused, loss_fused = run("fused_cross_entropy")
    np.testing.assert_allclose(loss_fused, loss_base, atol=1e-5, rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(st_base.params),
        jax.tree_util.tree_leaves(st_fused.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        )


# the acceptance receipt: at the 350m widths (d_model=1024, vocab=32768)
# the compiled fused train step has NO live [B, S, V]-shaped float
# intermediate, while the baseline provably does


def _step_hlo(loss, cfg, batch):
    import optax as _optax

    model = TransformerLM(cfg)
    # abstract state: lower/compile only need shapes+dtypes — materializing
    # ~350M real params on CPU would double this test for nothing
    state = jax.eval_shape(
        lambda key: TrainState.create(
            apply_fn=model.apply,
            params=model.init(key, batch[0])["params"],
            tx=_optax.adamw(1e-3, weight_decay=0.01),
        ),
        jax.random.PRNGKey(1),
    )
    compiled = make_train_step(loss=loss).lower(state, batch).compile()
    return compiled, state


def _logits_shapes(b, s, v):
    """Every HLO rendering a live [B, S, V] float could take: 3-D, and the
    (B*S, V) flattening XLA's dot output uses."""
    return [
        rf"(f32|bf16|f16)\[{b},{s},{v}\]",
        rf"(f32|bf16|f16)\[{b * s},{v}\]",
    ]


def test_350m_config_step_has_no_logits_intermediate():
    b, s = 1, 32
    cfg = TransformerConfig(
        vocab_size=32768, d_model=1024, n_layers=24, n_heads=16,
        max_seq_len=s, scan_layers=True,
    )
    toks = jax.random.randint(jax.random.PRNGKey(0), (b, s + 1), 0, 100)
    batch = (toks[:, :-1].astype(jnp.int32), toks[:, 1:].astype(jnp.int32))

    fused_compiled, state = _step_hlo("fused_cross_entropy", cfg, batch)
    txt = fused_compiled.as_text()
    for pat in _logits_shapes(b, s, cfg.vocab_size):
        assert not re.search(pat, txt), (
            f"fused step materializes a logits-shaped tensor ({pat})"
        )
    # (memory_analysis() is not asserted on: interpreter-mode Pallas keeps
    # full-array working copies per pallas_call, so CPU temp sizes do not
    # reflect the Mosaic VMEM behavior — the HLO shape sweep above is the
    # backend-honest form of the "no live logits" check)

    # positive control so the assertion above is falsifiable: the SAME
    # inspection finds the logits in a standard-loss step. Only (B, S, V)
    # matters to the shape sweep, so the control model is thin in width
    # and depth (a full-width baseline compile would double the test)
    thin = TransformerConfig(
        vocab_size=32768, d_model=64, n_layers=1, n_heads=4,
        max_seq_len=s, scan_layers=True,
    )
    base_compiled, _ = _step_hlo("cross_entropy", thin, batch)
    base_txt = base_compiled.as_text()
    assert any(
        re.search(p, base_txt) for p in _logits_shapes(b, s, 32768)
    ), "HLO inspection failed to find the baseline's logits tensor"


def test_350m_widths_loss_and_grads_match():
    """Fwd/bwd equivalence at the real 350m head widths (d_model=1024,
    vocab=32768 — the dimensions the blockwise kernels actually tile at
    scale), thin in rows to stay CPU-fast. The trainer-path wiring of the
    same op is covered by test_train_step_fused_matches_baseline."""
    h, w, y = _hwy(1, 16, 1024, 32768, seed=11)

    def mean_loss(fn):
        return lambda h, w: fn(h, w).mean()

    loss_b, dense = jax.value_and_grad(
        mean_loss(lambda h, w: _optax_loss(h, w, y)), argnums=(0, 1)
    )(h, w)
    loss_f, fused = jax.value_and_grad(
        mean_loss(
            lambda h, w: fused_cross_entropy(
                h, w, y, block_n=16, block_v=4096
            )
        ),
        argnums=(0, 1),
    )(h, w)
    np.testing.assert_allclose(
        float(loss_f), float(loss_b), atol=1e-5, rtol=1e-5
    )
    for name, a, b in zip(("dh", "dw"), dense, fused):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-3,
            err_msg=name,
        )

"""``benchmark/blocks/gqa_swiglu/`` tied to the program at a small size on the
CPU, for a grouped-query toy of each family's head ratio (16:8 as
InternLM2, 32:8 as Mistral): the model's logits, the tokens ``ServeEngine``
serves through its prefill and its cache, and the loss and gradients the
``Trainer``'s step sees."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import adamw, harness, program, serve_kind, train_kind, weights

BLOCK = harness.Block("gqa_swiglu")
reference = BLOCK.reference

RATIOS = [(16, 8), (32, 8)]


def toy(heads, kv):
    return reference.Shape(
        vocab_size=384, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=heads, num_key_value_heads=kv,
        intermediate_size=256, rope_theta=10000.0, rms_norm_eps=1e-5,
    )


def config(shape, **serve):
    cfg = {f: getattr(shape, f) for f in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "rope_theta", "rms_norm_eps")}
    cfg["initializer_range"] = 0.05
    cfg["train"] = {"compute_dtype": "float32", "remat_policy": "dots",
                    "kernels": False, "strategy": "DataParallel"}
    cfg["serve"] = {"compute_dtype": "float32", "weights_dtype": "float32",
                    "window": 64, **serve}
    return cfg


@pytest.mark.parametrize("heads,kv", RATIOS)
def test_logits_and_served_tokens(heads, kv):
    shape = toy(heads, kv)
    cfg = config(shape)
    tree = weights.make(reference.leaf_shapes(shape), 5, "float32", 0.05)
    params = BLOCK.program.to_program(tree, shape)
    model = BLOCK.program.model(cfg, "serve", 64)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, shape.vocab_size, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        theirs = model.apply({"params": params}, tokens[None])[0]
    ours = reference.logits(tree, jnp.asarray(tokens), shape)
    np.testing.assert_allclose(np.asarray(theirs), np.asarray(ours), atol=2e-4)

    # prefill (bucketed) and decode through the slot cache: every served
    # token is the reference's first choice
    engine = program.serve_engine(model, params, {"n_slots": 2, "tokens_per_launch": 4})
    prompts = [tokens[:13].tolist(), tokens[5:38].tolist(), tokens[:7].tolist()]
    for p in prompts:
        engine.submit(program.request(p, 9))
    done = sorted(engine.run_until_idle(), key=lambda c: c.request_id)
    served = [(list(c.prompt), list(c.tokens)) for c in done]
    assert [p for p, _ in served] == prompts
    gaps, compared = serve_kind.token_gaps(BLOCK, shape, tree, served, 64)
    assert compared == 27 and max(gaps) < 1e-3


@pytest.mark.parametrize("heads,kv", RATIOS)
def test_loss_and_gradients_of_a_trainer_step(heads, kv):
    shape = toy(heads, kv)
    cfg = config(shape)
    mix = {"batch": 4, "seq_len": 24, "steps_per_epoch": 2, "token_skew": 2.0,
           "adamw": {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
                     "eps": 1e-8, "weight_decay": 0.01}}
    from benchmark.lib import traffic

    arrays = traffic.train_tokens(mix, shape.vocab_size, 1)
    strat = program.strategy("DataParallel")
    loader = program.sharded_loader(arrays, 4, strat.mesh, 1)
    seen = []
    trainer = program.trainer(
        BLOCK.program.model(cfg, "train", 24), loader, cfg, mix, strat, 1,
        lambda step, loss: seen.append(float(loss)))
    spec = reference.leaf_shapes(shape)
    trainer.state = trainer.state.replace(params=weights.make(
        spec, 1, "float32", 0.05, convert=lambda t: BLOCK.program.to_program(t, shape)))
    loader.set_epoch(0)
    batch = next(iter(loader))
    state, metrics = trainer.train_step(trainer.state, batch)
    grads = jax.tree_util.tree_map(
        lambda m: m / 0.1, BLOCK.program.from_program(train_kind._moment(state.opt_state)))
    tree = weights.make(spec, 1, "float32", 0.05)
    loss, ref_grads = reference.grad_fn(shape)(
        tree, jnp.asarray(batch[0]), jnp.asarray(batch[1]))
    assert abs(float(metrics["loss"]) - loss) < 2e-5 * loss
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3,
            atol=2e-3 * float(jnp.abs(b).max()), err_msg=str(path))
    # one AdamW step of the reference lands where the program's did
    mu = jax.tree_util.tree_map(jnp.zeros_like, tree)
    nu = jax.tree_util.tree_map(jnp.zeros_like, tree)
    hyp = (1e-3, 0.9, 0.999, 1e-8, 0.01)
    new, _, _ = adamw.adamw(tree, ref_grads, mu, nu, jnp.asarray(1), hyp)
    start = weights.make(spec, 1, "float32", 0.05)
    for (path, a), b, s in zip(
            jax.tree_util.tree_leaves_with_path(BLOCK.program.from_program(state.params)),
            jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(start)):
        if float(jnp.abs(b - s).max()) == 0:
            continue
        moved = np.asarray(a - s)
        np.testing.assert_allclose(
            np.linalg.norm(moved), np.linalg.norm(np.asarray(b - s)), rtol=0.02,
            err_msg=str(path))

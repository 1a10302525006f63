"""dp x pp pipeline parallelism with microbatching.

Two schedules, both beyond the reference's no-interleave lesson
(``/root/reference/03.model_parallel.ipynb:830-833``):

- :class:`~...parallel.pipeline.GPipe` — heterogeneous stages (ResNet cut)
  on per-stage sub-mesh columns, microbatch fill/drain, gradient + BN-stat
  accumulation. Numerics verified against a single-device
  gradient-accumulation comparator doing the identical math.
- :class:`~...parallel.pipeline_spmd.PipelinedTransformerLM` — homogeneous
  transformer stages as ONE shard_map program (layer stack sharded over
  ``stage``, ppermute hops), numerics identical to the unpipelined model.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_training_tutorials_tpu.data import ShardedLoader, synthetic_lm
from pytorch_distributed_training_tutorials_tpu.models import resnet18
from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from pytorch_distributed_training_tutorials_tpu.parallel import (
    GPipe,
    PipelinedTransformerLM,
    PipelineParallel,
)
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
from pytorch_distributed_training_tutorials_tpu.train import Trainer


@pytest.fixture(scope="module")
def dp_pp_mesh(devices):
    return create_mesh({"data": 4, "stage": 2})


def _tiny_images(n=16, px=8, seed=0):
    rng = np.random.Generator(np.random.PCG64(seed))
    x = rng.standard_normal((n, px, px, 3)).astype(np.float32)
    y = jax.nn.one_hot(rng.integers(0, 10, n), 10).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def _single_device_accum_step(model, variables, xs, ys, lr):
    """Comparator: plain gradient accumulation over the same microbatches,
    BN statistics averaged across microbatches from step-start stats —
    exactly GPipe's update rule, with no pipeline."""
    params, stats = variables["params"], variables["batch_stats"]

    def loss_fn(p, xm, ym):
        out, upd = model.apply(
            {"params": p, "batch_stats": stats},
            xm,
            train=True,
            mutable=["batch_stats"],
        )
        return jnp.mean((out - ym) ** 2), upd["batch_stats"]

    g_acc, s_acc, losses = None, None, []
    for xm, ym in zip(xs, ys):
        (loss, new_stats), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, xm, ym
        )
        losses.append(loss)
        g_acc = g if g_acc is None else jax.tree_util.tree_map(jnp.add, g_acc, g)
        s_acc = (
            new_stats
            if s_acc is None
            else jax.tree_util.tree_map(jnp.add, s_acc, new_stats)
        )
    inv = 1.0 / len(xs)
    g_mean = jax.tree_util.tree_map(lambda t: t * inv, g_acc)
    s_mean = jax.tree_util.tree_map(lambda t: t * inv, s_acc)
    tx = optax.sgd(lr)
    updates, _ = tx.update(g_mean, tx.init(params), params)
    return (
        optax.apply_updates(params, updates),
        s_mean,
        float(jnp.mean(jnp.stack(losses))),
    )


@pytest.mark.slow
def test_gpipe_resnet18_matches_single_device(dp_pp_mesh):
    """dp(4) x pp(2), 4 microbatches: params, BN stats, and loss after one
    GPipe step equal the single-device gradient-accumulation step."""
    model = resnet18(num_classes=10, stem="cifar")
    x, y = _tiny_images(n=16)
    lr = 0.05

    pipe = GPipe.from_linen(
        model,
        x,
        devices=dp_pp_mesh,
        num_microbatches=4,
        loss="mse",
        optimizer=optax.sgd(lr),
        seed=0,
    )
    loss_pipe = float(pipe.train_step(x, y))

    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x))
    xs = [x[i * 4 : (i + 1) * 4] for i in range(4)]
    ys = [y[i * 4 : (i + 1) * 4] for i in range(4)]
    params_ref, stats_ref, loss_ref = _single_device_accum_step(
        model, variables, xs, ys, lr
    )

    np.testing.assert_allclose(loss_pipe, loss_ref, rtol=1e-5)
    # merge the per-stage trees back into full params/stats and compare.
    merged_params = {}
    merged_stats = {}
    for v in pipe.stage_vars:
        merged_params.update(jax.device_get(v["params"]))
        merged_stats.update(jax.device_get(v.get("batch_stats", {})))
    # atol 5e-5: microbatched gradient accumulation reassociates the f32
    # sums, so near-zero entries (where rtol is meaningless) carry a few
    # ulp-scale reorder noise — observed max |diff| ~2.5e-5 on this
    # backend, on 17/1728 elements of one conv kernel
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5
        ),
        merged_params,
        jax.device_get(params_ref),
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5
        ),
        merged_stats,
        jax.device_get(stats_ref),
    )


def test_gpipe_shard_shapes_and_placement(dp_pp_mesh):
    """Stage params replicate over their column's 4 data devices; microbatch
    activations shard 4-ways over data; param count is split-invariant."""
    model = resnet18(num_classes=10, stem="cifar")
    x, y = _tiny_images(n=16)
    pipe = GPipe.from_linen(
        model, x, devices=dp_pp_mesh, num_microbatches=4,
        loss="mse", optimizer=optax.sgd(0.05),
    )
    assert pipe.dp_size == 4
    col_ids = []
    for s, v in enumerate(pipe.stage_vars):
        leaf = jax.tree_util.tree_leaves(v["params"])[0]
        devs = sorted(d.id for d in leaf.sharding.device_set)
        assert len(devs) == 4  # one column of the 4x2 grid
        col_ids.append(tuple(devs))
    assert col_ids[0] != col_ids[1]  # disjoint columns
    # forward activations shard over data: 16 rows -> 4/device
    out = pipe.forward(x)
    assert out.shape == (16, 10)
    shard_rows = {s.data.shape[0] for s in out.addressable_shards}
    assert shard_rows == {4}
    # param-count invariance (the 25,557,032 lesson at ResNet-18 scale)
    full = model.init(jax.random.PRNGKey(0), jnp.asarray(x))["params"]
    total = sum(a.size for a in jax.tree_util.tree_leaves(full))
    assert sum(pipe.stage_param_counts()) == total


@pytest.mark.slow
def test_gpipe_trains(dp_pp_mesh):
    model = resnet18(num_classes=10, stem="cifar")
    x, y = _tiny_images(n=32, seed=1)
    pipe = GPipe.from_linen(
        model, x, devices=dp_pp_mesh, num_microbatches=4,
        loss="mse", optimizer=optax.sgd(0.01),
    )
    first = float(pipe.train_step(x, y))
    for _ in range(4):
        last = float(pipe.train_step(x, y))
    assert last < first


def test_gpipe_validates_microbatching(dp_pp_mesh):
    model = resnet18(num_classes=10, stem="cifar")
    x, y = _tiny_images(n=16)
    pipe = GPipe.from_linen(
        model, x, devices=dp_pp_mesh, num_microbatches=3,
        loss="mse", optimizer=optax.sgd(0.1),
    )
    with pytest.raises(ValueError, match="not divisible by 3 microbatches"):
        pipe.train_step(x, y)


# ---- single-program shard_map pipeline (homogeneous stages) ----------------


def _lm_cfg(**kw):
    base = dict(
        vocab_size=64, d_model=32, n_layers=4, n_heads=2, max_seq_len=64,
        scan_layers=True,
    )
    base.update(kw)
    return TransformerConfig(**base)


@pytest.mark.slow
def test_spmd_pipeline_forward_and_grads_match_unpipelined(dp_pp_mesh):
    """The GPipe schedule reorders compute, not math: logits and grads are
    identical to the plain scan-layers TransformerLM."""
    cfg = _lm_cfg()
    model = PipelinedTransformerLM(cfg, dp_pp_mesh, num_microbatches=4)
    ref = TransformerLM(cfg)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (16, 8), 0, cfg.vocab_size)
    variables = model.init(key, tokens)

    np.testing.assert_allclose(
        np.asarray(model.apply(variables, tokens)),
        np.asarray(ref.apply(variables, tokens)),
        rtol=2e-5,
        atol=2e-5,
    )

    def loss(apply_fn, params):
        logits = apply_fn({"params": params}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]
        ).mean()

    g_pipe = jax.grad(lambda p: loss(model.apply, p))(variables["params"])
    g_ref = jax.grad(lambda p: loss(ref.apply, p))(variables["params"])
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        g_pipe,
        g_ref,
    )


def test_spmd_pipeline_trainer_dp_pp(dp_pp_mesh):
    """Trainer + PipelineParallel: one jitted dp x pp train step; layer
    stack physically sharded over stage; loss decreases."""
    cfg = _lm_cfg()
    model = PipelinedTransformerLM(cfg, dp_pp_mesh, num_microbatches=4)
    strategy = PipelineParallel(dp_pp_mesh, num_microbatches=4)
    loader = ShardedLoader(
        synthetic_lm(size=256, seq_len=16, vocab_size=64), 16, dp_pp_mesh
    )
    trainer = Trainer(
        model, loader, optax.adam(3e-3), strategy=strategy,
        loss="cross_entropy",
    )
    first = trainer._run_epoch(0)
    last = trainer.train(3)
    assert last["loss"] < first["loss"]
    qk = trainer.state.params["layers"]["block"]["attn"]["q_proj"]["kernel"]
    # 4 stacked layers, 2 per stage resident
    assert qk.shape[0] == 4
    assert qk.sharding.spec[0] == "stage"
    assert qk.addressable_shards[0].data.shape[0] == 2
    mu = trainer.state.opt_state[0].mu["layers"]["block"]["attn"]["q_proj"][
        "kernel"
    ]
    assert mu.sharding.spec[0] == "stage"


def test_spmd_pipeline_rejects_bad_configs(dp_pp_mesh):
    with pytest.raises(ValueError, match="not divisible"):
        PipelinedTransformerLM(
            _lm_cfg(n_layers=3), dp_pp_mesh, num_microbatches=2
        )
    with pytest.raises(ValueError, match="dense blocks only"):
        PipelinedTransformerLM(
            dataclasses.replace(_lm_cfg(), moe_experts=4),
            dp_pp_mesh,
            num_microbatches=2,
        )


@pytest.mark.slow
def test_gpipe_dispatch_count_scales_with_microbatches(dp_pp_mesh):
    """Pin GPipe's dispatch model: the heterogeneous schedule is
    PYTHON-DRIVEN — train_step issues exactly n_stages*m forward and
    n_stages*m backward stage programs plus n_stages applies (separate
    XLA launches; microbatch hops add device_puts on top). On a runtime
    with per-launch cost L this floors a step at ~2*n*m*L regardless of
    compute (L on the chip: not measured;
    scripts/launch_overhead_probe.py) — the reason ManualPipeline (no
    microbatching, 2n+n launches) or the single-program pipeline_spmd
    (ONE launch) win on high-launch-cost runtimes, and why this schedule
    claims overlap only from async dispatch, not from fewer programs."""
    model = resnet18(num_classes=10, stem="cifar")
    x, y = _tiny_images(n=16)
    for m in (2, 4):
        pipe = GPipe.from_linen(
            model, x, devices=dp_pp_mesh, num_microbatches=m,
            loss="mse", optimizer=optax.sgd(0.05), seed=0,
        )
        counts = {"fwd": 0, "bwd": 0, "apply": 0}

        def wrap(fn, key):
            def inner(*a, **kw):
                counts[key] += 1
                return fn(*a, **kw)
            return inner

        pipe._fwd = [wrap(f, "fwd") for f in pipe._fwd]
        pipe._bwd_mid = [wrap(f, "bwd") for f in pipe._bwd_mid]
        pipe._bwd_last = wrap(pipe._bwd_last, "bwd")
        real_apply = pipe._apply_stage
        pipe._apply_stage = wrap(real_apply, "apply")

        pipe.train_step(x, y)
        n = pipe.num_stages
        # forward: every microbatch runs stages 0..n-2 eagerly (the last
        # stage's forward happens inside its bwd program)
        assert counts["fwd"] == (n - 1) * m, counts
        assert counts["bwd"] == n * m, counts
        assert counts["apply"] == n, counts

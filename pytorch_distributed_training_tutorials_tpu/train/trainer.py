"""Trainer + jitted SPMD train step.

Twin of the reference's ``Trainer`` (reference ``ddp_gpus.py:19-53``, torchrun
variant ``ddp_gpus_torchrun.py:16-49``): owns model/loader/optimizer, runs
epoch -> batch loops, logs the per-epoch line, calls ``set_epoch`` for the
reshuffle. The differences are the TPU-native ones (SURVEY.md section 7):

- ``_run_batch``'s zero_grad/forward/loss/backward/step
  (``ddp_gpus.py:34-39``) is one ``jax.jit``-compiled ``train_step`` with
  donated state; the DDP gradient allreduce is compiled in by XLA from the
  sharding layout (replicated params x batch-sharded data), overlapped with
  the backward like NCCL's bucketed hooks.
- no per-step H2D ``.to(device)`` calls (``ddp_gpus.py:47-48``): the loader
  already delivers mesh-sharded device arrays.
- loss *is* logged (the reference never logs it — SURVEY.md section 5.5), and
  the trainer reports steps/s and samples/s for the benchmark harness.

Loss functions mirror the reference's: ``cross_entropy``
(``F.cross_entropy``, ``ddp_gpus.py:37``) and ``mse`` (the model-parallel
lesson, ``03.model_parallel.ipynb:991``). ``fused_cross_entropy`` is the
same objective computed logits-free: the model is applied with
``return_hidden=True`` and :func:`..ops.fused_loss.fused_cross_entropy`
streams the final hidden states against the ``lm_head`` kernel blockwise,
so the (B, S, vocab) logits tensor — the largest activation of an LM train
step — never exists in HBM.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import optax
from flax import core, struct

from pytorch_distributed_training_tutorials_tpu.models.moe import moe_aux_loss
from pytorch_distributed_training_tutorials_tpu.ops.fused_loss import (
    fused_cross_entropy,
)
from pytorch_distributed_training_tutorials_tpu.parallel.data_parallel import (
    DataParallel,
)
from pytorch_distributed_training_tutorials_tpu.obs.metrics import MetricsLogger
from pytorch_distributed_training_tutorials_tpu.utils import chaos as chaos_lib
from pytorch_distributed_training_tutorials_tpu.utils.logging import epoch_line
from pytorch_distributed_training_tutorials_tpu.utils.profiling import annotate

_DONE = object()  # what next() gives when a loader is exhausted


class TrainState(struct.PyTreeNode):
    """Params + optimizer state + (optional) batch stats, one pytree.

    A minimal flax-style train state: everything the jitted step mutates lives
    here so the whole bundle can be donated and resharded as a unit.
    """

    step: jnp.ndarray
    apply_fn: Any = struct.field(pytree_node=False)
    params: core.FrozenDict
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    opt_state: optax.OptState
    batch_stats: core.FrozenDict | None = None

    @classmethod
    def create(cls, *, apply_fn, params, tx, batch_stats=None):
        return cls(
            step=jnp.zeros((), jnp.int32),
            apply_fn=apply_fn,
            params=params,
            tx=tx,
            opt_state=tx.init(params),
            batch_stats=batch_stats,
        )


def create_train_state(
    model,
    optimizer: optax.GradientTransformation,
    sample_input,
    *,
    strategy,
    seed: int = 0,
) -> TrainState:
    """Init model variables replicated on the mesh and wrap in a TrainState.

    The replicated placement is the twin of DDP's construction-time param
    broadcast from rank 0 (reference ``ddp_gpus.py:32``): every device starts
    from identical params (same PRNG key -> same init, placed replicated).
    """
    key = jax.random.PRNGKey(seed)
    # One row per data-parallel replica: models whose forward shards the batch
    # explicitly (shard_map, e.g. ring attention) need init shapes divisible
    # by the mesh axes; params themselves are batch-size independent.
    sample = jnp.asarray(
        sample_input[: max(1, getattr(strategy, "num_devices", 1))]
    )
    # Per-parameter placement: replicated for data parallelism, rule-driven
    # for tensor/hybrid parallelism — one strategy interface either way.
    abstract = jax.eval_shape(model.init, key, sample)
    out_shardings = strategy.variable_shardings(abstract)
    variables = jax.jit(model.init, out_shardings=out_shardings)(key, sample)
    params = variables["params"]
    batch_stats = variables.get("batch_stats")
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=optimizer, batch_stats=batch_stats
    )
    return strategy.shard_state(state)


def _fused_ce_loss(params, hidden, targets):
    """Mean logits-free cross entropy: final hidden states streamed against
    the model's own ``lm_head`` kernel (cast to the activation dtype, the
    same cast ``nn.Dense(dtype=cfg.dtype)`` applies before its matmul)."""
    if "lm_head" not in params:
        raise ValueError(
            'loss="fused_cross_entropy" needs a model with an lm_head '
            "Dense whose forward supports return_hidden=True "
            "(models.transformer.TransformerLM)"
        )
    w = params["lm_head"]["kernel"]
    return fused_cross_entropy(
        hidden, w.astype(hidden.dtype), targets
    ).mean()


def _compute_loss(loss: str, logits, targets):
    if loss == "cross_entropy":
        if targets.ndim == logits.ndim:  # one-hot / soft targets
            return optax.softmax_cross_entropy(logits, targets).mean()
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()
    if loss == "mse":
        return jnp.mean((logits - targets) ** 2)
    raise ValueError(f"unknown loss {loss!r}")


def _make_loss_fn(
    loss: str, has_batch_stats: bool, aux_loss_weight: float,
    model_kwargs: dict | None = None,
):
    """The single definition of the training objective, shared by the plain
    step, the epoch scan, and the gradient-accumulation step — one place
    owns the batch_stats/mutable/aux-loss contract.

    ``model_kwargs`` are extra keywords forwarded verbatim to every model
    apply — e.g. ``{"adapter_ids": tid}`` to pin a LoRA fine-tune
    (:mod:`..adapters`) to one tenant row. They are closed over (trace-time
    constants), not per-batch data."""

    fused = loss == "fused_cross_entropy"

    def loss_fn(params, state: TrainState, batch):
        x, y = batch
        variables = {"params": params}
        mutable = []
        kwargs = dict(model_kwargs) if model_kwargs else {}
        if has_batch_stats:
            variables["batch_stats"] = state.batch_stats
            mutable.append("batch_stats")
            kwargs["train"] = True
        if aux_loss_weight:
            mutable.append("losses")
        if fused:
            # fused tail: the model stops at the final-norm hidden states;
            # the lm_head matmul happens inside the blockwise loss kernel
            kwargs["return_hidden"] = True
        if mutable:
            out, updates = state.apply_fn(
                variables, x, mutable=mutable, **kwargs
            )
        else:
            out, updates = state.apply_fn(variables, x, **kwargs), {}
        with jax.named_scope("loss"):
            if fused:
                loss_val = _fused_ce_loss(params, out, y)
            else:
                loss_val = _compute_loss(loss, out, y)
            if aux_loss_weight:
                loss_val = loss_val + aux_loss_weight * moe_aux_loss(
                    updates
                )
        return loss_val, updates.get("batch_stats")

    return loss_fn


def _apply_update(
    state: TrainState,
    grads,
    loss_val,
    new_stats,
    has_batch_stats,
    skip_nonfinite: bool = False,
    chaos=None,
):
    """The optimizer-update tail shared by the plain and gradient-
    accumulation steps — one place owns tx.update/apply/replace/metrics.

    ``skip_nonfinite`` adds the ISSUE 9 skip-step guard: when the loss or
    ANY gradient leaf is non-finite, the whole update is elided via a
    ``jnp.where`` tree-select — params, opt_state and batch_stats come out
    bitwise equal to the incoming state and ``step`` does not advance. The
    finite flag is DATA (graftcheck ``traced-control-flow`` clean) and the
    guard sits AFTER ``tx.update``, so it composes with any optax chain
    and with :func:`..ops.fused_optim.fused_adamw` unchanged (the fused
    kernel's aliased mu/nu buffers are reverted the same way — XLA copies
    live donated inputs, so the old values are still available to the
    select). Metrics gain a ``"skipped"`` 0/1 device scalar ONLY when the
    guard is on — guard-off programs keep a byte-identical jaxpr.

    ``chaos`` (a :class:`..utils.chaos.ChaosConfig` poisoning grads)
    injects NaN gradients at the configured ``TrainState.step`` BEFORE the
    update — the fault the guard is tested against, landing exactly where
    a real non-finite backward reduction would."""
    if chaos is not None and chaos.poisons_grads:
        grads = chaos_lib.poison_grads(grads, state.step, chaos.nan_grad_step)
    with jax.named_scope("optimizer"):
        updates, new_opt_state = state.tx.update(
            grads, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
    metrics = {"loss": loss_val}
    if skip_nonfinite:
        ok = jnp.isfinite(loss_val)
        for g in jax.tree_util.tree_leaves(grads):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))

        def select(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), new, old
            )

        new_params = select(new_params, state.params)
        new_opt_state = select(new_opt_state, state.opt_state)
        if has_batch_stats and new_stats is not None:
            new_stats = select(new_stats, state.batch_stats)
        step_inc = ok.astype(state.step.dtype)
        metrics["skipped"] = jnp.int32(1) - step_inc.astype(jnp.int32)
    else:
        step_inc = 1
    new_state = state.replace(
        step=state.step + step_inc,
        params=new_params,
        opt_state=new_opt_state,
        batch_stats=new_stats if has_batch_stats else state.batch_stats,
    )
    return new_state, metrics


def _train_step_fn(
    loss: str = "cross_entropy",
    has_batch_stats: bool = False,
    aux_loss_weight: float = 0.0,
    model_kwargs: dict | None = None,
    skip_nonfinite: bool = False,
    chaos=None,
):
    """The raw (unjitted) SPMD train step, shared by :func:`make_train_step`
    (jit per step — streaming loaders) and :func:`make_epoch_scan` (one jit
    per epoch — device-resident datasets). ``skip_nonfinite``/``chaos``
    thread through to :func:`_apply_update` (the skip-step guard and the
    NaN-grad injector)."""
    loss_fn = _make_loss_fn(
        loss, has_batch_stats, aux_loss_weight, model_kwargs
    )

    def step_fn(state: TrainState, batch):
        (loss_val, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params, state, batch)
        return _apply_update(
            state, grads, loss_val, new_stats, has_batch_stats,
            skip_nonfinite=skip_nonfinite, chaos=chaos,
        )

    return step_fn


def make_train_step(
    loss: str = "cross_entropy",
    has_batch_stats: bool = False,
    aux_loss_weight: float = 0.0,
    grad_accum_steps: int = 1,
    model_kwargs: dict | None = None,
    skip_nonfinite: bool = False,
    chaos=None,
):
    """Build the jitted SPMD train step (donated state).

    One compiled program per step replaces the reference's
    zero_grad/forward/loss/backward/allreduce/step sequence
    (``ddp_gpus.py:34-39``). Gradients come out replicated — XLA inserts the
    ICI allreduce during the backward because params are replicated while the
    batch is sharded.

    ``aux_loss_weight`` > 0 collects the model's sown ``"losses"`` collection
    (MoE load-balancing) and adds it, weighted, to the objective.

    ``loss="fused_cross_entropy"`` trains an LM through the logits-free
    blockwise head+loss (:mod:`..ops.fused_loss`) — same objective as
    ``"cross_entropy"``, minus the (B, S, vocab) logits activation. Also
    accepted by :func:`make_epoch_scan` and the gradient-accumulation step
    (they all share one loss definition).

    ``grad_accum_steps`` > 1 splits the batch into that many microbatches
    inside the compiled step (a ``lax.scan``), averaging gradients (and
    BatchNorm statistics) before ONE optimizer update — the standard trade
    of peak activation memory for step time when the global batch exceeds
    HBM. Batch dim 0 must divide evenly; for the strided microbatch split to
    stay evenly spread over a ``data``-sharded batch, the *per-device* row
    count must also divide by ``grad_accum_steps`` (the Trainer validates
    this where the mesh width is known).

    ``model_kwargs`` forwards extra trace-time keywords to every model
    apply (see :func:`_make_loss_fn`) — the LoRA fine-tune path pins
    ``{"adapter_ids": tid}`` this way.

    ``skip_nonfinite`` turns on the skip-step guard (see
    :func:`_apply_update`): a non-finite loss/grad leaves the returned
    state bitwise equal to the input (step included) and the metrics dict
    gains a ``"skipped"`` 0/1 device scalar. With gradient accumulation
    the guard checks the AVERAGED gradients — one poisoned microbatch
    skips the whole optimizer step, matching what folding it in would
    have corrupted. ``chaos`` injects the tested fault
    (:class:`..utils.chaos.ChaosConfig`).
    """
    if grad_accum_steps < 1:
        raise ValueError(f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
    if grad_accum_steps == 1:
        return jax.jit(
            _train_step_fn(
                loss, has_batch_stats, aux_loss_weight, model_kwargs,
                skip_nonfinite=skip_nonfinite, chaos=chaos,
            ),
            donate_argnums=0,
        )

    loss_fn = _make_loss_fn(
        loss, has_batch_stats, aux_loss_weight, model_kwargs
    )

    def step_fn(state: TrainState, batch):
        n = grad_accum_steps
        b = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if b % n:
            raise ValueError(
                f"batch dim 0 ({b}) not divisible by "
                f"grad_accum_steps ({n})"
            )
        # strided split (microbatch m = rows m::n): with dim 0 sharded over
        # `data` in contiguous per-device blocks, every microbatch stays
        # evenly spread over all devices (a contiguous (n, B/n) reshape
        # would hand each microbatch to a fraction of the mesh and force a
        # reshard per scan iteration)
        micro = jax.tree_util.tree_map(
            lambda a: a.reshape(
                a.shape[0] // n, n, *a.shape[1:]
            ).swapaxes(0, 1),
            batch,
        )

        def body(acc, mb):
            g_acc, s_acc, l_acc = acc
            (loss_val, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params, state, mb)
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, grads)
            if has_batch_stats:
                s_acc = jax.tree_util.tree_map(jnp.add, s_acc, new_stats)
            return (g_acc, s_acc, l_acc + loss_val), None

        zeros_g = jax.tree_util.tree_map(jnp.zeros_like, state.params)
        zeros_s = (
            jax.tree_util.tree_map(
                lambda a: jnp.zeros_like(a, jnp.float32), state.batch_stats
            )
            if has_batch_stats
            else None
        )
        (g_sum, s_sum, l_sum), _ = jax.lax.scan(
            body, (zeros_g, zeros_s, jnp.float32(0)), micro
        )
        inv = 1.0 / n
        grads = jax.tree_util.tree_map(lambda g: g * inv, g_sum)
        new_stats = (
            jax.tree_util.tree_map(
                lambda s, old: (s * inv).astype(old.dtype),
                s_sum,
                state.batch_stats,
            )
            if has_batch_stats
            else None
        )
        return _apply_update(
            state, grads, l_sum * inv, new_stats, has_batch_stats,
            skip_nonfinite=skip_nonfinite, chaos=chaos,
        )

    return jax.jit(step_fn, donate_argnums=0)


def make_epoch_scan(
    loss: str = "cross_entropy",
    has_batch_stats: bool = False,
    aux_loss_weight: float = 0.0,
    transform=None,
    unroll: int = 1,
    pregather: bool = False,
    skip_nonfinite: bool = False,
    chaos=None,
):
    """Build a jitted *whole-epoch* program: ``lax.scan`` of the train step
    over a device-resident dataset.

    ``epoch_fn(state, idx, data) -> (state, losses)`` where ``idx`` is the
    epoch's ``(steps, global_batch)`` index matrix
    (:meth:`..data.resident.DeviceResidentLoader.epoch_index_array`), ``data``
    the resident dataset arrays, and ``losses`` the per-step loss trace. The
    batch gather (and optional ``transform``, e.g. uint8 -> normalized float)
    happens inside the scan body, so XLA fuses it into the step. Replaces the
    reference's per-step ``for ... in dataloader`` hot loop
    (``ddp_gpus.py:46-49``) with one program launch per epoch.

    ``unroll`` passes through to ``lax.scan``: unrolling the step body lets
    XLA amortize while-loop bookkeeping and the carried-state copies across
    iterations (measured round 4 on v5e: unroll=8 removed ~4% of step time
    on the ResNet-18 bs512 leg — the loop-boundary ``copy-start/copy-done``
    pairs halved). Costs compile time roughly linearly; 1 (no unroll) keeps
    test-suite compiles fast.

    ``skip_nonfinite``/``chaos`` thread through to the scanned step (same
    guard as :func:`make_train_step`; the per-step ``"skipped"`` scalar is
    not carried out of the scan — a skipped step is visible as
    ``state.step`` advancing by less than the steps run).

    ``pregather`` hoists the row gather OUT of the scan body: one epoch-wide
    take reshapes the resident dataset to ``(steps, B, ...)`` and the scan
    consumes contiguous leading-axis slices instead of doing a 512-row
    gather per iteration, at the cost of a transient epoch-sized HBM copy
    (uint8 MNIST x 5 fused epochs ~ 0.3 GB). Measured on the v5e headline
    workload it is NEUTRAL TO SLIGHTLY WORSE (46.5k -> 45.7k img/s at
    unroll=1; 48.0k -> 47.7k at unroll=8, min-of-3) — the in-body gather
    fuses well there. Kept because the trade can flip for datasets whose
    gather does not fuse (host-padded layouts, very wide rows); measure
    before enabling. What DID move the headline is ``unroll=8`` on this
    scan (round 5).
    """
    step_fn = _train_step_fn(
        loss, has_batch_stats, aux_loss_weight,
        skip_nonfinite=skip_nonfinite, chaos=chaos,
    )

    def epoch_fn(state: TrainState, idx, data):
        def body(state, batch):
            if transform is not None:
                batch = transform(*batch)
            state, metrics = step_fn(state, batch)
            return state, metrics["loss"]

        if pregather:
            stacked = tuple(a[idx] for a in data)  # (T, B, ...) one take
            state, losses = jax.lax.scan(
                body, state, stacked, unroll=unroll
            )
        else:
            def gather_body(state, idx_step):
                return body(state, tuple(a[idx_step] for a in data))

            state, losses = jax.lax.scan(
                gather_body, state, idx, unroll=unroll
            )
        return state, losses

    return jax.jit(epoch_fn, donate_argnums=0)


def make_eval_step(loss: str = "cross_entropy", has_batch_stats: bool = False):
    """Jitted eval step: per-batch (summed per-sample loss, correct count,
    sample count), weighted by a per-row validity ``mask``.

    ``mask`` (shape ``(B,)``) zeroes out wrap-padded duplicate rows (the
    equal-shard padding the reference's DistributedSampler silently counts —
    the framework computes the pad, so eval can mask it;
    :meth:`..data.loader.ShardedLoader.valid_mask`). ``correct`` is an
    argmax-accuracy count for integer-label cross-entropy and 0 otherwise
    (regression has no accuracy).

    A ``"fused_cross_entropy"`` trainer evaluates through the standard
    logits path: eval needs the argmax anyway, and one forward per eval
    batch has no optimizer state competing for HBM — same objective,
    same numbers.
    """
    if loss == "fused_cross_entropy":
        loss = "cross_entropy"

    def eval_fn(state: TrainState, batch, mask):
        x, y = batch
        variables = {"params": state.params}
        if has_batch_stats:
            variables["batch_stats"] = state.batch_stats
            logits = state.apply_fn(variables, x, train=False)
        else:
            logits = state.apply_fn(variables, x)
        mask = mask.astype(jnp.float32)
        classification = loss == "cross_entropy" and y.ndim < logits.ndim
        if classification:
            # per-label stats (for an LM, labels = every token position);
            # the row mask broadcasts over the label positions
            mask_rows = mask.reshape(mask.shape[0], *([1] * (y.ndim - 1)))
            per_label = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            )
            loss_sum = (per_label * mask_rows).sum()
            correct = jnp.sum(
                (jnp.argmax(logits, -1) == y) * mask_rows
            ).astype(jnp.int32)
            count = (jnp.ones_like(y, jnp.float32) * mask_rows).sum()
        else:
            # per-sample loss over feature dims; accuracy undefined
            if loss == "mse":
                feat_axes = tuple(range(1, y.ndim))
                per_sample = jnp.mean(
                    (logits - y) ** 2, axis=feat_axes
                ) if feat_axes else (logits - y) ** 2
            else:  # soft-target cross entropy: (B, ...) per-position losses
                per_sample = optax.softmax_cross_entropy(logits, y)
            # broadcast the row mask over any remaining positions (e.g. an
            # LM's (B, T) soft-target losses)
            mask_rows = mask.reshape(
                mask.shape[0], *([1] * (per_sample.ndim - 1))
            )
            loss_sum = (per_sample * mask_rows).sum()
            correct = jnp.zeros((), jnp.int32)
            count = (jnp.ones_like(per_sample) * mask_rows).sum()
        return loss_sum, correct, count

    return jax.jit(eval_fn)


class Trainer:
    """Epoch/batch training loop over a sharded loader.

    API twin of the reference Trainer (``ddp_gpus.py:19-53``)::

        trainer = Trainer(model, loader, optax.sgd(1e-2), strategy=dp)
        trainer.train(max_epochs)
    """

    def __init__(
        self,
        model,
        train_loader,
        optimizer: optax.GradientTransformation,
        *,
        strategy=None,  # DataParallel | TensorParallel | compatible
        loss: str = "cross_entropy",
        aux_loss_weight: float = 0.0,
        grad_accum_steps: int = 1,
        seed: int = 0,
        log_every: int | None = None,
        defer_host_fetch: bool = False,
        scan_unroll: int = 1,
        pregather: bool = False,
        metrics: MetricsLogger | None = None,
        quiet: bool = False,
        on_step=None,
        on_epoch=None,
        skip_nonfinite: bool = False,
        chaos=None,
        rollback_spike_factor: float | None = None,
        rollback_patience: int = 2,
        rollback_ema: float = 0.9,
        flight=None,
        sentry=None,
    ):
        self.model = model
        self.loader = train_loader
        self.strategy = strategy if strategy is not None else DataParallel(
            train_loader.mesh
        )
        # the loader-owned seam (no reaching into dataset internals): any
        # loader exposing sample_batch() works — streaming, resident, custom
        sample = train_loader.sample_batch()
        if isinstance(sample, tuple):
            sample = sample[0]
        self.state = create_train_state(
            model, optimizer, sample, strategy=self.strategy, seed=seed
        )
        self.has_batch_stats = self.state.batch_stats is not None
        # -- ISSUE 9 training guardrails ------------------------------------
        # skip_nonfinite: jnp.where-elide the optimizer update on any
        # non-finite loss/grad (see _apply_update) — the per-step
        # "skipped" 0/1 device scalar rides the MetricsLogger batched
        # drain (log_step extra), never a per-step sync.
        # chaos: a utils.chaos.ChaosConfig — deterministic fault injection
        # (NaN grads at a step, spiked monitor loss) for the tests.
        # rollback_spike_factor: when the monitored loss exceeds
        # factor x its EMA (or is non-finite) for `rollback_patience`
        # consecutive observations, restore the latest `save()` target and
        # continue (restore-and-continue: the data position — self.epoch —
        # is kept, only the state rolls back). The monitor observes host
        # floats: per step on the streaming path, per chunk on the chunked
        # path, per epoch on the scanned path — opting in costs that fetch
        # cadence (documented price; rollback needs loss visibility).
        self.skip_nonfinite = skip_nonfinite
        self.chaos = chaos
        if rollback_spike_factor is not None and rollback_spike_factor <= 1:
            raise ValueError(
                f"rollback_spike_factor must be > 1 (None = off), got "
                f"{rollback_spike_factor}"
            )
        if rollback_patience < 1:
            raise ValueError(
                f"rollback_patience must be >= 1, got {rollback_patience}"
            )
        if not 0.0 <= rollback_ema < 1.0:
            raise ValueError(
                f"rollback_ema must be in [0, 1), got {rollback_ema}"
            )
        self._rb_factor = rollback_spike_factor
        self._rb_patience = rollback_patience
        self._rb_decay = rollback_ema
        self._rb_ema = None  # EMA of healthy monitored losses
        self._rb_strikes = 0  # consecutive spike observations
        self._monitor_steps = 0  # monotonic host counter, never replays
        self._dispatches = 0  # monotonic step-dispatch counter (batch chaos)
        self.rollbacks = 0
        self._last_ckpt = None  # latest save() target (rollback restores it)
        self.train_step = make_train_step(
            loss=loss,
            has_batch_stats=self.has_batch_stats,
            aux_loss_weight=aux_loss_weight,
            grad_accum_steps=grad_accum_steps,
            skip_nonfinite=skip_nonfinite,
            chaos=chaos,
        )
        if grad_accum_steps > 1 and getattr(
            train_loader, "device_arrays", None
        ) is not None:
            raise ValueError(
                "grad_accum_steps applies to the per-step path; the "
                "device-resident epoch scan already amortizes memory — use "
                "a streaming ShardedLoader for gradient accumulation"
            )
        if grad_accum_steps > 1:
            if train_loader.global_batch % grad_accum_steps:
                # the compiled step would reject this at trace time anyway
                # (make_train_step's batch-dim check) — fail at construction
                raise ValueError(
                    f"global batch ({train_loader.global_batch}) not "
                    f"divisible by grad_accum_steps ({grad_accum_steps})"
                )
            # strategy.num_devices is the DATA-axis width by interface
            # contract (every strategy returns mesh.shape[data axis], not
            # the total device count — see DataParallel.num_devices), so
            # it is the right divisor on hybrid meshes too (ADVICE r3)
            d = self.strategy.num_devices
            per_dev = train_loader.global_batch // max(d, 1)
            if per_dev % grad_accum_steps:
                # semantically correct either way (microbatches are the same
                # rows), but each scan iteration pays a reshard of its
                # microbatch across the data axis — warn, don't break
                import warnings

                warnings.warn(
                    f"per-device batch ({per_dev}) not divisible by "
                    f"grad_accum_steps ({grad_accum_steps}): microbatches "
                    "cannot stay evenly spread over the data axis and will "
                    "reshard every accumulation step (slow, not wrong)",
                    stacklevel=2,
                )
        self.log_every = log_every
        # scan_unroll: lax.scan unroll factor for the compiled epoch/chunk
        # scans (see make_epoch_scan) — a perf knob for long device-resident
        # or chunked runs; leave 1 where compile time matters more (tests).
        # Baked into the cached scan at first trace — set it here, not after
        # an epoch has run.
        if scan_unroll < 1:
            raise ValueError(f"scan_unroll must be >= 1, got {scan_unroll}")
        self.scan_unroll = scan_unroll
        # pregather: hoist the per-step row gather out of the compiled
        # epoch scan (make_epoch_scan pregather) — a perf knob for
        # device-resident datasets, costing a transient epoch-sized copy
        self.pregather = pregather
        # defer_host_fetch: end chunked epochs with block_until_ready
        # (completion only) instead of a per-epoch loss fetch — standard
        # TPU practice to keep host-device syncs out of the training loop.
        # Losses stay on device in ``last_epoch_losses``; fetch after
        # training via :meth:`fetch_last_loss`.
        self.defer_host_fetch = defer_host_fetch
        # metrics: every number and console line the loop produces flows
        # through one MetricsLogger (obs/metrics.py) — the verbose step
        # print and the structured record are the same fetch, and the
        # logger honors defer_host_fetch at epoch boundaries. ``quiet``
        # silences console output (bench runs) without losing events.
        self.metrics = metrics if metrics is not None else MetricsLogger(
            quiet=quiet, defer_host_fetch=defer_host_fetch, flight=flight
        )
        # flight recorder (ISSUE 10): skip-step observations reach it
        # through the MetricsLogger drain above (the "skipped" extra
        # already rides the batched fetch — no new per-step sync);
        # rollbacks stamp directly in _do_rollback (host-side already).
        self._flight = flight
        if flight is not None and self.metrics.flight is None:
            self.metrics.flight = flight
        # contract sentry (ISSUE 19): None = off (no behavior change at
        # all). On, each epoch attributes compile events to its phase
        # label and the TrainState tree is walked once per epoch for
        # host-numpy leaves — a restored-without-shardings checkpoint
        # re-uploads the whole model EVERY step (the device_materialize
        # trap); fresh data batches are deliberately NOT checked, their
        # H2D is the job.
        self._sentry = sentry
        # host-side hook points, called OUTSIDE traced code (graftcheck-
        # clean by construction): on_step(step, loss_device_scalar) after
        # each dispatched step/chunk, on_epoch(metrics_dict) after each
        # epoch. Hooks must not fetch if they care about throughput.
        self.on_step = on_step
        self.on_epoch = on_epoch
        self.last_epoch_losses = None  # device array, chunked path only
        self.loss_name = loss
        self.aux_loss_weight = aux_loss_weight
        self.grad_accum_steps = grad_accum_steps
        self.last_epoch_metrics: dict = {}
        self.epoch = 0  # next epoch to run; advanced by train(), restored
        self._eval_step = None
        self._epoch_scan = None
        self._chunk_scan = None

    def _epoch_metrics(self, epoch: int, loss, steps: int, dt: float) -> dict:
        """Shared metric dict + per-epoch log line for both epoch paths
        (streaming and scanned) — one place defines the keys/format."""
        m = {
            "epoch": epoch,
            "loss": float(loss) if loss is not None else float("nan"),
            "steps": steps,
            "steps_per_sec": steps / dt if dt > 0 else float("inf"),
            "samples_per_sec": steps * self.loader.global_batch / dt
            if dt > 0
            else float("inf"),
        }
        self.metrics.log_epoch(m)
        if self.on_epoch is not None:
            self.on_epoch(m)
        return m

    def _run_epoch_scanned(self, epoch: int) -> dict:
        """One program launch for the whole epoch (device-resident loader)."""
        loader = self.loader
        if self._epoch_scan is None:
            self._epoch_scan = make_epoch_scan(
                loss=self.loss_name,
                has_batch_stats=self.has_batch_stats,
                aux_loss_weight=self.aux_loss_weight,
                transform=loader.transform,
                unroll=self.scan_unroll,
                pregather=self.pregather,
                skip_nonfinite=self.skip_nonfinite,
                chaos=self.chaos,
            )
        self.metrics.say(
            epoch_line(
                self.strategy.num_devices, epoch,
                loader.per_device_batch, len(loader),
            )
        )
        idx = loader.epoch_index_array(epoch)
        t0 = time.perf_counter()
        self.state, losses = self._epoch_scan(
            self.state, idx, loader.device_arrays
        )
        loss = float(losses[-1])  # host fetch: the honest end-of-epoch sync
        if self._rb_factor is not None:
            self._monitor_loss(loss)  # per-epoch granularity on this path
        dt = time.perf_counter() - t0
        return self._epoch_metrics(epoch, loss, len(loader), dt)

    def run_epochs_fused(self, first_epoch: int, n_epochs: int) -> dict:
        """Run ``n_epochs`` consecutive epochs as ONE compiled program
        (device-resident loaders only): the per-epoch index matrices are
        stacked into a single scan, so launch + final-fetch overhead is paid
        once per *run* instead of once per epoch. Epoch-seeded reshuffle
        semantics are identical — each epoch's indices come from the same
        ``set_epoch`` permutation the per-epoch path uses.

        Returns the last epoch's metrics (with aggregate ``samples_per_sec``
        over the fused region — the honest end-to-end rate).
        """
        loader = self.loader
        if getattr(loader, "device_arrays", None) is None:
            raise ValueError("run_epochs_fused requires a device-resident loader")
        if self._epoch_scan is None:
            self._epoch_scan = make_epoch_scan(
                loss=self.loss_name,
                has_batch_stats=self.has_batch_stats,
                aux_loss_weight=self.aux_loss_weight,
                transform=loader.transform,
                unroll=self.scan_unroll,
                pregather=self.pregather,
                skip_nonfinite=self.skip_nonfinite,
                chaos=self.chaos,
            )
        idx = jnp.concatenate(
            [
                loader.epoch_index_array(first_epoch + e)
                for e in range(n_epochs)
            ],
            axis=0,
        )
        steps = len(loader)
        t0 = time.perf_counter()
        self.state, losses = self._epoch_scan(
            self.state, idx, loader.device_arrays
        )
        losses = jax.device_get(losses)  # one host fetch for the whole run
        dt = time.perf_counter() - t0
        for e in range(n_epochs):
            epoch_losses = losses[e * steps : (e + 1) * steps]
            self.metrics.say(
                f"  epoch {first_epoch + e}: loss "
                f"{float(epoch_losses[-1]):.4f} (fused scan)"
            )
        self.epoch = first_epoch + n_epochs
        m = self._epoch_metrics(
            first_epoch + n_epochs - 1,
            float(losses[-1]),
            steps * n_epochs,
            dt,
        )
        m["steps"] = steps  # per-epoch steps, like the per-epoch path
        self.last_epoch_metrics = m  # keep the train()-path contract
        return m

    def _run_epoch_chunked(self, epoch: int) -> dict:
        """Streaming twin of the epoch scan: each prefetched multi-step
        chunk (:meth:`..data.streaming.ChunkedStreamingLoader.iter_chunks`)
        trains as ONE compiled ``lax.scan`` launch, while the next chunk's
        gather + H2D runs in the background — the per-step dispatch and
        transfer latency the round-2 profile flagged amortizes over the
        chunk length."""
        loader = self.loader
        loader.set_epoch(epoch)
        self.metrics.say(
            epoch_line(
                self.strategy.num_devices, epoch,
                loader.per_device_batch, len(loader),
            )
        )
        if self._chunk_scan is None:
            step_fn = _train_step_fn(
                self.loss_name, self.has_batch_stats, self.aux_loss_weight,
                skip_nonfinite=self.skip_nonfinite, chaos=self.chaos,
            )
            transform = loader.transform

            def chunk_scan(state, chunk):
                def body(state, batch):
                    if transform is not None:
                        batch = transform(*batch)
                    state, metrics = step_fn(state, batch)
                    return state, metrics["loss"]

                return jax.lax.scan(body, state, chunk, unroll=self.scan_unroll)

            # two compilations at most: full chunks + a shorter tail chunk
            self._chunk_scan = jax.jit(chunk_scan, donate_argnums=0)
        t0 = time.perf_counter()
        losses = []
        steps = 0
        next_log = self.log_every or 0
        chunks = iter(loader.iter_chunks())
        while True:
            with annotate("loader_next", step=steps):
                chunk = next(chunks, _DONE)
            if chunk is _DONE:
                break
            steps += jax.tree_util.tree_leaves(chunk)[0].shape[0]
            with annotate("dispatch", step=steps):
                self.state, chunk_losses = self._chunk_scan(
                    self.state, chunk
                )
            losses.append(chunk_losses)
            if self.log_every and steps >= next_log:
                # per-chunk granularity (a chunk is one compiled launch;
                # per-step logs would force a D2H sync into the scan) —
                # costs one loss fetch, so only when log_every opted in
                self.metrics.log_step(steps, chunk_losses[-1], verbose=True)
                next_log = steps + self.log_every
            if self.on_step is not None:
                self.on_step(steps, chunk_losses[-1])
            if self._rb_factor is not None:
                # per-chunk granularity (one fetch per compiled launch)
                if self._monitor_loss(float(chunk_losses[-1])):
                    break  # rolled back: abandon the rest of this epoch
        self.last_epoch_losses = losses[-1] if losses else None
        with annotate("epoch_sync", step=steps):
            if self.defer_host_fetch:
                # completion sync only — no D2H (see defer_host_fetch in
                # __init__)
                if losses:
                    jax.block_until_ready(losses[-1])
                loss = None
            else:
                loss = float(losses[-1][-1]) if losses else None
        dt = time.perf_counter() - t0
        return self._epoch_metrics(epoch, loss, steps, dt)

    def fetch_last_loss(self) -> float:
        """Fetch the deferred final loss of the last chunked epoch (a D2H
        read — call AFTER throughput-sensitive work)."""
        if self.last_epoch_losses is None:
            raise ValueError("no deferred losses recorded")
        return float(self.last_epoch_losses[-1])

    def _run_epoch(self, epoch: int) -> dict:
        if self._sentry is not None:
            self._sentry.set_phase(f"epoch {epoch}")
            self._sentry.check_args(self.state, label="train_state")
        if getattr(self.loader, "device_arrays", None) is not None:
            return self._run_epoch_scanned(epoch)
        if (
            getattr(self.loader, "iter_chunks", None) is not None
            and self.grad_accum_steps == 1
        ):
            # grad accumulation composes with the per-step path only (its
            # microbatching lives inside make_train_step)
            return self._run_epoch_chunked(epoch)
        self.loader.set_epoch(epoch)  # reference ddp_gpus.py:45
        self.metrics.say(
            epoch_line(
                self.strategy.num_devices,
                epoch,
                self.loader.per_device_batch,
                len(self.loader),
            )
        )
        t0 = time.perf_counter()
        loss = None
        steps = 0
        batches = iter(self.loader)
        while True:
            # the loop's own next(): the wait for a batch is inside the span
            with annotate("loader_next", step=steps):
                batch = next(batches, _DONE)
            if batch is _DONE:
                break
            if not isinstance(batch, tuple):
                batch = (batch,)
            self._dispatches += 1
            if self.chaos is not None and self.chaos.poisons_batch:
                batch = chaos_lib.maybe_poison_batch(
                    self.chaos, self._dispatches, batch
                )
            with annotate("dispatch", step=steps):
                self.state, metrics = self.train_step(self.state, batch)
            loss = metrics["loss"]
            steps += 1
            # device scalar retained un-fetched; the verbose line is the
            # log_every opt-in and costs its one historical loss fetch.
            # The skip-step counter (guard on only) rides the same batched
            # drain as the loss — still no per-step sync.
            self.metrics.log_step(
                steps, loss,
                verbose=bool(self.log_every)
                and steps % self.log_every == 0,
                extra=(
                    {"skipped": metrics["skipped"]}
                    if "skipped" in metrics else None
                ),
            )
            if self.on_step is not None:
                self.on_step(steps, loss)
            if self._rb_factor is not None:
                # rollback opted in: per-step loss visibility is its price
                self._monitor_loss(float(loss))
        with annotate("epoch_sync", step=steps):
            jax.block_until_ready(self.state.params)
        dt = time.perf_counter() - t0
        return self._epoch_metrics(epoch, loss, steps, dt)

    def train(self, max_epochs: int) -> dict:
        """Run up to epoch ``max_epochs`` (reference ``ddp_gpus.py:51-53``).

        Starts from ``self.epoch``, so a trainer restored from a checkpoint
        continues where it left off instead of retraining from scratch (the
        reference is restart-safe only by being stateless — SURVEY.md
        section 5.3/5.4; this closes that gap).
        """
        if self.epoch >= max_epochs:
            self.metrics.say(
                f"train: already at epoch {self.epoch} >= {max_epochs}, "
                "nothing to run"
            )
            # same key shape as a real epoch so metric consumers don't branch
            self.last_epoch_metrics = {
                "epoch": self.epoch, "loss": float("nan"), "steps": 0,
                "steps_per_sec": 0.0, "samples_per_sec": 0.0,
                "skipped": True,
            }
            return self.last_epoch_metrics
        for epoch in range(self.epoch, max_epochs):
            self.last_epoch_metrics = self._run_epoch(epoch)
            self.epoch = epoch + 1
        return self.last_epoch_metrics

    # -- loss-spike rollback (ISSUE 9 guardrail) ---------------------------
    def _monitor_loss(self, loss_value: float) -> bool:
        """Feed one host-float loss observation to the spike monitor;
        returns True when it triggered a rollback. A spike is a value
        exceeding ``rollback_spike_factor`` x the EMA of healthy
        observations (or any non-finite value); ``rollback_patience``
        consecutive spikes trigger. Spiky observations are NEVER folded
        into the EMA (a sustained spike must not normalize itself), and
        the monitor's host step counter is monotonic across rollbacks —
        a chaos-injected spike keyed to it cannot re-fire after the
        restore (the livelock a state.step-keyed injector would hit)."""
        import math

        self._monitor_steps += 1
        if self.chaos is not None:
            loss_value = chaos_lib.host_spike_loss(
                loss_value, self._monitor_steps, self.chaos
            )
        spike = not math.isfinite(loss_value) or (
            self._rb_ema is not None
            and loss_value > self._rb_factor * self._rb_ema
        )
        if spike:
            self._rb_strikes += 1
            if self._rb_strikes >= self._rb_patience:
                self._do_rollback(loss_value)
                return True
            return False
        self._rb_strikes = 0
        d = self._rb_decay
        self._rb_ema = (
            loss_value if self._rb_ema is None
            else d * self._rb_ema + (1.0 - d) * loss_value
        )
        return False

    def _do_rollback(self, loss_value: float) -> None:
        """Restore the latest ``save()`` target and continue training.

        Restore-and-continue semantics: the TrainState (params/opt/step)
        rolls back; the data position (``self.epoch``) does NOT — the
        batches that drove the spike are skipped, not replayed, which is
        both the standard divergence recovery and what keeps a
        deterministic spike from re-firing. The monitor resets (EMA and
        strikes) so post-restore losses re-seed it."""
        if self._last_ckpt is None:
            raise RuntimeError(
                "loss-spike rollback triggered but no checkpoint exists — "
                "call save() at least once (e.g. per epoch) when "
                "rollback_spike_factor is set"
            )
        epoch_now = self.epoch
        self.restore(self._last_ckpt)
        self.epoch = epoch_now  # keep the data position (skip, don't replay)
        self.rollbacks += 1
        self._rb_strikes = 0
        self._rb_ema = None
        if self._flight is not None:
            # host-side already (the monitor observes fetched floats) —
            # stamping adds no sync; auto-dumps when dump_path is set
            self._flight.rollback(
                step=self._monitor_steps, loss=loss_value
            )
        self.metrics.say(
            f"  rollback #{self.rollbacks}: loss {loss_value:.4g} spiked "
            f">{self._rb_factor:g}x EMA for {self._rb_patience} obs — "
            f"restored {self._last_ckpt}"
        )

    @property
    def steps_skipped(self) -> int:
        """Total skip-step elisions recorded so far (``skip_nonfinite``
        path). Flushes the metrics logger — i.e. performs its batched
        drain fetch — so call at receipt/epoch boundaries, not per step."""
        self.metrics.flush()
        return int(
            sum(e.get("skipped", 0) for e in self.metrics.step_events())
        )

    # -- checkpoint / resume (SURVEY.md section 5.4 gap fix) ---------------
    def _state_tree(self) -> dict:
        import numpy as np

        tree = {
            "step": self.state.step,
            "params": self.state.params,
            "opt_state": self.state.opt_state,
            # host scalar, not a device array: a per-process
            # SingleDeviceSharding leaf would break multi-host orbax saves
            "epoch": np.asarray(self.epoch, np.int32),
        }
        if self.has_batch_stats:
            tree["batch_stats"] = self.state.batch_stats
        return tree

    def save(self, path, keep: int | None = None) -> None:
        """Sharded checkpoint of params/optimizer/step/epoch (orbax —
        each host writes only its addressable shards). ATOMIC either way
        (ISSUE 9): a crash mid-save can never corrupt the latest restore
        target, which the rollback leg and restart-resume both depend on.

        ``keep=None`` (default): ``path`` is one checkpoint, overwritten
        atomically — the new tree lands in ``path + ".tmp"`` first, the
        previous checkpoint is parked at ``path + ".old"`` while the tmp
        renames into place, then the parked copy is deleted. At every
        instant either ``path`` or ``path + ".old"`` is a COMPLETE
        checkpoint (:meth:`restore` falls back to ``.old`` when ``path``
        is missing). Plain ``save_checkpoint`` would not give this:
        orbax's ``force=True`` removes the old directory BEFORE writing.

        ``keep=K``: ``path`` is a rotation directory of
        ``ckpt-{step:08d}`` children; each save writes a fresh child
        (tmp + rename — atomic because the target never pre-exists) and
        prunes all but the newest K. :meth:`restore` pointed at the
        directory resolves the newest child.

        Either form records the written target as the rollback restore
        point (``rollback_spike_factor``)."""
        import os
        import shutil

        from pytorch_distributed_training_tutorials_tpu.parallel.auto import (
            save_checkpoint,
        )

        path = os.path.abspath(os.fspath(path))
        if keep is not None:
            if keep < 1:
                raise ValueError(f"keep must be >= 1 (None = single), got {keep}")
            os.makedirs(path, exist_ok=True)
            name = f"ckpt-{int(self.state.step):08d}"
            target = os.path.join(path, name)
            tmp = target + ".tmp"
            if os.path.exists(tmp):
                shutil.rmtree(tmp)  # stale crash residue
            save_checkpoint(tmp, self._state_tree())
            if os.path.exists(target):
                shutil.rmtree(target)  # re-save at the same step
            os.rename(tmp, target)
            kids = sorted(
                d for d in os.listdir(path)
                if d.startswith("ckpt-") and not d.endswith(".tmp")
            )
            for d in kids[:-keep]:
                shutil.rmtree(os.path.join(path, d))
        else:
            tmp, old = path + ".tmp", path + ".old"
            for stale in (tmp, old):
                if os.path.exists(stale):
                    shutil.rmtree(stale)  # crash residue from a prior save
            save_checkpoint(tmp, self._state_tree())
            if os.path.exists(path):
                os.rename(path, old)
            os.rename(tmp, path)
            if os.path.exists(old):
                shutil.rmtree(old)
        self._last_ckpt = path

    @staticmethod
    def _resolve_ckpt(path) -> str:
        """Map a restore path onto the atomic-save layout: a rotation
        directory resolves to its newest ``ckpt-*`` child; a missing
        single-checkpoint path falls back to the ``.old`` parked copy
        (present exactly when a crash hit the rename window)."""
        import os

        path = os.path.abspath(os.fspath(path))
        if os.path.isdir(path):
            kids = sorted(
                d for d in os.listdir(path)
                if d.startswith("ckpt-") and not d.endswith(".tmp")
            )
            if kids:
                return os.path.join(path, kids[-1])
        if not os.path.exists(path) and os.path.exists(path + ".old"):
            return path + ".old"
        return path

    def restore(self, path) -> None:
        """Restore in place, preserving the current sharding layout (the
        template tree's shardings drive orbax's placement). Accepts a
        plain checkpoint, a ``save(keep=K)`` rotation directory (newest
        child wins), or a crash-windowed single path (``.old``
        fallback)."""
        from pytorch_distributed_training_tutorials_tpu.parallel.auto import (
            restore_checkpoint,
        )

        restored = restore_checkpoint(
            self._resolve_ckpt(path), like=self._state_tree()
        )
        self.epoch = int(restored.pop("epoch"))
        self.state = self.state.replace(**restored)

    # -- evaluation (the reference never evaluates — SURVEY.md 5.5) --------
    def evaluate(self, eval_loader=None) -> dict:
        """Mean loss (the trainer's configured loss) + accuracy (for
        integer-label classification; 0.0 otherwise) over ``eval_loader``
        (default: the training loader).

        Wrap-padded duplicate rows (the equal-shard padding SPMD requires)
        are **masked out** when the loader can identify them
        (:meth:`..data.loader.ShardedLoader.valid_mask`), so metrics are
        unbiased on datasets that don't divide evenly — unlike the
        reference, whose DistributedSampler silently double-counts the pad.

        The returned ``"samples"`` counts *label positions*: for sequence
        targets (an LM's (B, T) labels) that is rows x tokens, not rows.
        Per-batch sums are float32 on device (exact up to 2^24 labels per
        batch); the cross-batch accumulation happens on host in float64.
        """
        import numpy as np

        from jax.sharding import NamedSharding, PartitionSpec

        loader = eval_loader if eval_loader is not None else self.loader
        if self._eval_step is None:
            self._eval_step = make_eval_step(
                self.loss_name, self.has_batch_stats
            )
        has_mask = hasattr(loader, "valid_mask")
        if has_mask and loader.axis in loader.mesh.shape:
            mask_sharding = NamedSharding(
                loader.mesh, PartitionSpec(loader.axis)
            )
        elif has_mask:
            # loader on a mesh without its batch axis (replicated batches,
            # e.g. a stage-only mesh with a custom batch_spec)
            mask_sharding = NamedSharding(loader.mesh, PartitionSpec())
        else:
            mask_sharding = None
        # accumulate device arrays; convert once after the loop so eval
        # dispatch stays async (a float() per batch would sync every step)
        losses, corrects, counts = [], [], []
        mask_cache: dict = {}  # padding lives in the tail steps; interior
        # steps share one all-ones mask — transfer each distinct mask once

        def device_mask(step, rows):
            if not has_mask:
                key = b"ones"
                if key not in mask_cache:
                    mask_cache[key] = jnp.ones((rows,), jnp.float32)
                return mask_cache[key]
            m = loader.valid_mask(step).astype(np.float32)
            key = m.tobytes()
            if key not in mask_cache:
                mask_cache[key] = jax.device_put(m, mask_sharding)
            return mask_cache[key]

        for step, batch in enumerate(loader):
            if not isinstance(batch, tuple) or len(batch) != 2:
                raise ValueError("evaluate() requires (x, y) batches")
            mask = device_mask(step, batch[0].shape[0])
            ls, c, n = self._eval_step(self.state, batch, mask)
            losses.append(ls)
            corrects.append(c)
            counts.append(n)
        loss_sum = float(sum(float(l) for l in jax.device_get(losses)))
        correct = int(sum(int(c) for c in jax.device_get(corrects)))
        seen = int(sum(float(n) for n in jax.device_get(counts)))
        return {
            "loss": loss_sum / max(seen, 1),
            "accuracy": correct / max(seen, 1),
            "samples": seen,
        }

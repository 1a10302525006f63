"""Inter-layer (pipeline) model parallelism: the 03-notebook lessons, TPU-native.

Reference semantics being reproduced (SURVEY.md C14/C15):

- ``ToyModel``: ``net1`` on cuda:0, ``net2`` on cuda:1, explicit
  ``x.to("cuda:1")`` hop in forward (``03.model_parallel.ipynb:440-450``),
  full train step crossing the boundary in backward (``:532-542``).
- ``ModelParallelResNet50``: conv1..layer2 on cuda:0, layer3..fc on cuda:1,
  one batch flows stage0 -> stage1 with **no microbatch interleave**
  (``:807-834``, ``:830-833``) — stage 0 idles while stage 1 computes, which
  is exactly what the reference's benchmark (C17) measures against single-GPU.

TPU-native design: each stage is its own jitted XLA program committed to its
device; the activation hop is an explicit ``jax.device_put`` (ICI transfer on
real hardware — the twin of the reference's P2P copy). The backward re-crosses
the boundaries in reverse. Stage backward uses **rematerialization**: instead
of shipping vjp residuals between separately-compiled programs, each stage's
backward recomputes its forward under ``jax.vjp`` — the standard TPU trade of
FLOPs for HBM bandwidth/residency.

Parameters are *partitioned*, not replicated: each device holds only its
stage's variable subtree (the reference's memory-splitting motivation),
verified by the param-count invariance test (25,557,032 summed across stages).
"""

from __future__ import annotations

import inspect
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from pytorch_distributed_training_tutorials_tpu.parallel.mesh import (
    DATA_AXIS,
    STAGE_AXIS,
)


def partition_variables(
    variables: dict, partition: Callable[[str], int], num_stages: int
) -> list[dict]:
    """Split a flax variables dict into per-stage dicts by top-level module key.

    ``partition`` maps a top-level module name (e.g. ``"conv1"``,
    ``"layer_groups_2_0"``, ``"fc"``) to its stage index. Every collection
    (params, batch_stats, ...) is split the same way. A stage method touching
    a variable assigned elsewhere fails loudly at trace time — the same
    guarantee the reference gets from per-device tensors.
    """
    out: list[dict] = [{} for _ in range(num_stages)]
    for coll, tree in variables.items():
        for name, sub in tree.items():
            s = partition(name)
            if not 0 <= s < num_stages:
                raise ValueError(f"partition({name!r}) -> {s} out of range")
            out[s].setdefault(coll, {})[name] = sub
    return out


def _method_takes_train(method) -> bool:
    return "train" in inspect.signature(method).parameters


def linen_stage_fn(model, method, *, train: bool = True) -> Callable:
    """Wrap a linen stage method as ``fn(variables, x) -> (out, updates)``.

    ``updates`` is a dict of mutated non-param collections (BN
    ``batch_stats``) or ``None``.
    """
    takes_train = _method_takes_train(method)

    def fn(variables, x):
        kwargs = {"train": train} if takes_train else {}
        mutable = [c for c in variables if c != "params"] if train else False
        if mutable:
            out, upd = model.apply(
                variables, x, method=method, mutable=mutable, **kwargs
            )
            return out, upd
        return model.apply(variables, x, method=method, **kwargs), None

    return fn


class ManualPipeline:
    """N sequential stages on N devices with explicit activation hops.

    ``stage_fns[i](variables_i, x) -> (out, updates_or_None)``; the last
    stage's output feeds the loss. Usage (twin of the reference's cells 12/26
    train loops)::

        pipe = ManualPipeline.from_linen(
            model, sample_x, devices=jax.devices()[:2],
            loss="mse", optimizer=optax.sgd(1e-3))
        out = pipe.forward(x)             # 2 programs + 1 hop
        loss = pipe.train_step(x, y)      # backward re-crosses the hop
    """

    def __init__(
        self,
        stage_fns: Sequence[Callable],
        stage_vars: Sequence[dict],
        devices: Sequence[jax.Device] | None = None,
        *,
        loss: str = "mse",
        optimizer: optax.GradientTransformation | None = None,
        eval_stage_fns: Sequence[Callable] | None = None,
    ):
        if devices is None:
            devices = jax.devices()[: len(stage_fns)]
        if len(stage_fns) != len(stage_vars):
            raise ValueError("one variables tree per stage required")
        if len(devices) < len(stage_fns):
            raise ValueError(
                f"{len(stage_fns)} stages but only {len(devices)} devices"
            )
        if loss not in ("mse", "cross_entropy"):
            raise ValueError(f"unknown loss {loss!r}")
        self.num_stages = len(stage_fns)
        self.devices = list(devices[: self.num_stages])
        self.stage_fns = list(stage_fns)
        # Commit each stage's variables to its device — the .to(f"cuda:{i}")
        # twin (reference 03.model_parallel.ipynb:812-827).
        self.stage_vars = [
            jax.device_put(v, d) for v, d in zip(stage_vars, self.devices)
        ]
        self.loss_name = loss
        self.tx = optimizer
        if optimizer is not None:
            self.opt_states = [
                jax.jit(optimizer.init)(v.get("params", {}))
                for v in self.stage_vars
            ]
            self._upd = jax.jit(self._opt_update)
        self._fwd = [jax.jit(fn) for fn in self.stage_fns]
        # Eval-mode programs (BN running averages) for inference forward.
        self._eval_fwd = (
            [jax.jit(fn) for fn in eval_stage_fns]
            if eval_stage_fns is not None
            else self._fwd
        )
        self._bwd_last = jax.jit(self._stage_bwd_last)
        # Stage 0 never needs the cotangent w.r.t. the raw input batch, so its
        # backward differentiates w.r.t. params only.
        self._bwd_mid = [
            jax.jit(self._make_stage_bwd(i, need_dx=i > 0))
            for i in range(self.num_stages - 1)
        ]

    @classmethod
    def from_linen(
        cls,
        model,
        sample_input,
        *,
        methods: Sequence | None = None,
        partition: Callable[[str], int] | None = None,
        devices=None,
        train: bool = True,
        seed: int = 0,
        **kwargs,
    ) -> "ManualPipeline":
        """Build from a linen model exposing ``stage0``/``stage1`` methods and
        a ``stage_partition(name) -> stage`` rule (ToyModel, ResNet)."""
        if methods is None:
            methods = [model.stage0, model.stage1]
        if partition is None:
            partition = model.stage_partition
        x = jnp.asarray(sample_input)
        variables = model.init(jax.random.PRNGKey(seed), x)
        stage_vars = partition_variables(dict(variables), partition, len(methods))
        stage_fns = [linen_stage_fn(model, m, train=train) for m in methods]
        eval_fns = [linen_stage_fn(model, m, train=False) for m in methods]
        return cls(stage_fns, stage_vars, devices, eval_stage_fns=eval_fns, **kwargs)

    # -- forward ----------------------------------------------------------
    def forward(self, x) -> jax.Array:
        """Inference forward (eval mode — BN running averages): stage i ->
        device hop -> stage i+1.

        The ``jax.device_put`` between stages is the explicit twin of the
        reference's ``x.to("cuda:1")`` (``03.model_parallel.ipynb:831``).
        """
        for i in range(self.num_stages):
            x = jax.device_put(x, self.devices[i])
            x, _ = self._eval_fwd[i](self.stage_vars[i], x)
        return x

    # -- loss -------------------------------------------------------------
    def _loss_fn(self, out, y):
        if self.loss_name == "mse":
            return ((out - y.astype(out.dtype)) ** 2).mean()
        if y.ndim == out.ndim:
            return optax.softmax_cross_entropy(out, y).mean()
        return optax.softmax_cross_entropy_with_integer_labels(out, y).mean()

    # -- backward ---------------------------------------------------------
    def _stage_bwd_last(self, variables, x, y):
        """Last stage: loss + grads wrt (params, stage input). Remat forward."""
        fn = self.stage_fns[-1]
        params = variables.get("params", {})
        rest = {k: v for k, v in variables.items() if k != "params"}

        def f(p, x_):
            out, upd = fn({"params": p, **rest}, x_)
            return self._loss_fn(out, y), upd

        loss, vjp_fn, upd = jax.vjp(f, params, x, has_aux=True)
        dparams, dx = vjp_fn(jnp.ones_like(loss))
        return loss, dparams, dx, upd

    def _make_stage_bwd(self, i: int, *, need_dx: bool):
        fn = self.stage_fns[i]

        def bwd(variables, x, ct):
            params = variables.get("params", {})
            rest = {k: v for k, v in variables.items() if k != "params"}

            if need_dx:
                def f(p, x_):
                    return fn({"params": p, **rest}, x_)

                _, vjp_fn, upd = jax.vjp(f, params, x, has_aux=True)
                dparams, dx = vjp_fn(ct)
                return dparams, dx, upd

            def f_params(p):
                return fn({"params": p, **rest}, x)

            _, vjp_fn, upd = jax.vjp(f_params, params, has_aux=True)
            (dparams,) = vjp_fn(ct)
            return dparams, None, upd

        return bwd

    def _opt_update(self, grads, opt_state, params):
        updates, new_opt = self.tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt

    def _apply_stage(self, i: int, grads, upd) -> None:
        v = dict(self.stage_vars[i])
        if self.tx is not None:
            v["params"], self.opt_states[i] = self._upd(
                grads, self.opt_states[i], v["params"]
            )
        if upd:
            v.update(upd)
        self.stage_vars[i] = v

    def train_step(self, x, y) -> jax.Array:
        """One optimizer step across all stages (reference ``:532-542``).

        Forward hops device-to-device saving stage inputs; backward walks the
        stages in reverse, each stage rematerializing its forward, handing the
        input-cotangent back across the boundary (the reference's backward
        P2P re-crossing), and applying its optimizer update in place.
        """
        if self.tx is None:
            raise ValueError("construct with optimizer=... to train")
        stage_inputs = []
        a = x
        for i in range(self.num_stages):
            a = jax.device_put(a, self.devices[i])
            stage_inputs.append(a)
            if i < self.num_stages - 1:
                a, _ = self._fwd[i](self.stage_vars[i], a)
        y = jax.device_put(y, self.devices[-1])

        loss, grads, ct, upd = self._bwd_last(
            self.stage_vars[-1], stage_inputs[-1], y
        )
        self._apply_stage(self.num_stages - 1, grads, upd)
        for i in range(self.num_stages - 2, -1, -1):
            ct = jax.device_put(ct, self.devices[i])
            grads, ct, upd = self._bwd_mid[i](
                self.stage_vars[i], stage_inputs[i], ct
            )
            self._apply_stage(i, grads, upd)
        return loss

    # -- introspection ----------------------------------------------------
    def stage_param_counts(self) -> list[int]:
        """Per-stage parameter counts (sums to the unsplit model's count —
        the 25,557,032 invariance check, reference cells 20/22)."""
        from pytorch_distributed_training_tutorials_tpu.models.utils import model_size

        return [model_size(v.get("params", {})) for v in self.stage_vars]

    def placement_audit(self) -> list[str]:
        """Device audit lines, twin of 03's param device/dtype audit (cell 4)."""
        return [
            f"stage {i}: {n:,} params on {d}"
            for i, (n, d) in enumerate(
                zip(self.stage_param_counts(), self.devices)
            )
        ]


def _tree_add(acc, tree):
    if tree is None:
        return acc
    if acc is None:
        return tree
    return jax.tree_util.tree_map(jnp.add, acc, tree)


def _tree_scale(tree, factor: float):
    if tree is None:
        return None
    return jax.tree_util.tree_map(lambda t: t * factor, tree)


class GPipe(ManualPipeline):
    """Microbatched dp x pp pipeline over a ``{'data': D, 'stage': S}`` mesh,
    for *heterogeneous* stages (the ResNet cut).

    Where :class:`ManualPipeline` reproduces the reference lesson exactly —
    one whole device per stage, one batch, stage 0 idle while stage 1 runs
    (``/root/reference/03.model_parallel.ipynb:830-833``) — ``GPipe`` is the
    production schedule the lesson motivates, composed with data parallelism:

    - each stage occupies one *column* of the device grid (its own sub-mesh
      with a ``data`` axis): stage params replicate over the column, and the
      per-stage gradient allreduce over ``data`` is compiled into each
      stage's backward by XLA, exactly as in pure DP.
    - the batch splits into ``num_microbatches`` microbatches that fill and
      drain the pipeline; stage programs live on disjoint device columns, so
      async dispatch CAN execute different microbatches concurrently — but
      the schedule itself is PYTHON-DRIVEN: ``train_step`` issues
      ``(n-1)*m`` forward + ``n*m`` backward stage programs + ``n`` applies
      as separate XLA launches (pinned by
      ``tests/test_gpipe.py::test_gpipe_dispatch_count_scales_with_
      microbatches``), plus a ``device_put`` per microbatch hop. On a
      runtime whose per-launch cost L is large this floors the step at
      ~``2*n*m*L`` regardless of compute (L on the chip: not measured —
      ``scripts/launch_overhead_probe.py`` is the probe).
      Choose by runtime: homogeneous layer stacks -> :mod:`.pipeline_spmd`
      (ONE compiled program, microbatching inside ``lax.scan``); direct
      low-launch-cost hosts with heterogeneous stages -> this class;
      lesson parity / no microbatching -> :class:`ManualPipeline`
      (``3n`` launches).
    - gradients (and BatchNorm statistics) accumulate across microbatches
      and apply once per step, averaged — numerically the step is plain
      gradient accumulation, verified against a single-device comparator in
      ``tests/test_gpipe.py``.

    Heterogeneous stages cannot ride a single ``shard_map`` program (no
    common stacked-parameter axis to shard over ``stage`` — see
    :mod:`.pipeline_spmd` for the homogeneous single-program schedule), so
    each stage is its own XLA program committed to its column; the
    microbatch hop is an ICI transfer between neighboring columns.

    Build with ``GPipe.from_linen(model, x, devices=mesh,
    num_microbatches=M, ...)`` — the mesh rides the ``devices`` slot.
    """

    def __init__(
        self,
        stage_fns: Sequence[Callable],
        stage_vars: Sequence[dict],
        mesh: Mesh,
        *,
        num_microbatches: int,
        data_axis: str = DATA_AXIS,
        stage_axis: str = STAGE_AXIS,
        **kwargs,
    ):
        if not isinstance(mesh, Mesh):
            raise TypeError(
                "GPipe places stages on a jax.sharding.Mesh with "
                f"'{data_axis}' and '{stage_axis}' axes; got {type(mesh)}"
            )
        if stage_axis not in mesh.shape:
            raise ValueError(f"mesh has no {stage_axis!r} axis: {mesh.shape}")
        num_stages = mesh.shape[stage_axis]
        if num_stages < len(stage_fns):
            raise ValueError(
                f"{len(stage_fns)} stages but mesh {stage_axis!r} axis is "
                f"{num_stages}"
            )
        if num_microbatches < 1:
            raise ValueError("num_microbatches must be >= 1")
        self.mesh = mesh
        self.num_microbatches = num_microbatches
        s_ax = mesh.axis_names.index(stage_axis)
        rep, act = [], []
        for s in range(len(stage_fns)):
            col = np.take(mesh.devices, s, axis=s_ax).reshape(-1)
            sub = Mesh(col, (data_axis,))
            rep.append(NamedSharding(sub, PartitionSpec()))
            act.append(NamedSharding(sub, PartitionSpec(data_axis)))
        self.act_shardings = act
        super().__init__(stage_fns, stage_vars, rep, **kwargs)

    @property
    def dp_size(self) -> int:
        return self.act_shardings[0].mesh.size

    def forward(self, x) -> jax.Array:
        """Inference forward: full batch, stage i column -> stage i+1 column
        (each hop reshards ``data``-split activations to the next column)."""
        for i in range(self.num_stages):
            x = jax.device_put(x, self.act_shardings[i])
            x, _ = self._eval_fwd[i](self.stage_vars[i], x)
        return x

    def _microbatches(self, arr):
        m = self.num_microbatches
        b = arr.shape[0]
        if b % m:
            raise ValueError(f"batch {b} not divisible by {m} microbatches")
        mbs = b // m
        if mbs % self.dp_size:
            raise ValueError(
                f"microbatch {mbs} rows not divisible by dp width "
                f"{self.dp_size}"
            )
        return [arr[i * mbs : (i + 1) * mbs] for i in range(m)]

    def train_step(self, x, y) -> jax.Array:
        """One optimizer step: GPipe fill (all microbatch forwards), drain
        (all microbatch backwards), then one averaged update per stage."""
        if self.tx is None:
            raise ValueError("construct with optimizer=... to train")
        n, m = self.num_stages, self.num_microbatches
        xs, ys = self._microbatches(x), self._microbatches(y)

        stage_inputs = [[None] * m for _ in range(n)]
        for mb in range(m):
            a = xs[mb]
            for i in range(n):
                a = jax.device_put(a, self.act_shardings[i])
                stage_inputs[i][mb] = a
                if i < n - 1:
                    a, _ = self._fwd[i](self.stage_vars[i], a)

        grad_acc: list = [None] * n
        upd_acc: list = [None] * n
        losses = []
        for mb in range(m):
            y_mb = jax.device_put(ys[mb], self.act_shardings[-1])
            loss, grads, ct, upd = self._bwd_last(
                self.stage_vars[-1], stage_inputs[-1][mb], y_mb
            )
            losses.append(loss)
            grad_acc[-1] = _tree_add(grad_acc[-1], grads)
            upd_acc[-1] = _tree_add(upd_acc[-1], upd)
            for i in range(n - 2, -1, -1):
                ct = jax.device_put(ct, self.act_shardings[i])
                grads, ct, upd = self._bwd_mid[i](
                    self.stage_vars[i], stage_inputs[i][mb], ct
                )
                grad_acc[i] = _tree_add(grad_acc[i], grads)
                upd_acc[i] = _tree_add(upd_acc[i], upd)

        inv = 1.0 / m
        for i in range(n):
            self._apply_stage(
                i, _tree_scale(grad_acc[i], inv), _tree_scale(upd_acc[i], inv)
            )
        return jnp.mean(jnp.stack([jnp.asarray(l) for l in losses]))

"""Single-program SPMD pipeline parallelism: microbatched GPipe in shard_map.

Beyond-parity capability. The reference's pipeline lesson is a 2-stage split
with **no microbatch interleave** — one batch flows stage0 -> stage1 while
stage 0 idles (``/root/reference/03.model_parallel.ipynb:830-833``);
:class:`.pipeline.ManualPipeline` is that literal lesson twin. This module is
the production shape the lesson motivates: a GPipe fill/drain schedule with
``M`` microbatches over a ``{'data': D, 'stage': S}`` mesh, composed *with*
data parallelism, compiled as **one** XLA program.

TPU-native design (the scaling-book pipelining recipe):

- the transformer's layer stack is built with ``nn.scan``
  (``scan_layers=True``), so every block parameter has a leading
  ``n_layers`` axis. Sharding that axis over ``stage`` puts a contiguous
  block of ``n_layers / S`` layers on each stage — pipeline placement *is* a
  sharding annotation, no wrapper modules.
- inside :func:`~jax.experimental.shard_map.shard_map`, each tick of a
  ``lax.scan`` runs every stage in parallel on its resident layers; the
  activation hop to the next stage is a ``lax.ppermute`` along ``stage``
  (ICI neighbor transfer on hardware). ``M + S - 1`` ticks drain the
  pipeline — the familiar GPipe bubble, amortized by ``M``.
- data parallelism rides the ``data`` axis of the same mesh: the microbatch
  rows are sharded over it, and XLA inserts the gradient allreduce exactly
  as in pure DP. dp x pp needs no new code, just the mesh.
- backward is ``jax.grad`` straight through the shard_map (ppermute
  transposes to the reverse hop) — forward and backward compile into the
  same program, overlap scheduled by XLA.

Numerics are *identical* to the unpipelined model: the schedule reorders
computation, not math (microbatches are rows of the same batch; the loss is
the same mean over all rows).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_training_tutorials_tpu.utils.compat import (
    shard_map_nocheck,
)

from pytorch_distributed_training_tutorials_tpu.parallel.mesh import (
    DATA_AXIS,
    STAGE_AXIS,
)


def spmd_pipeline(
    stage_fn,
    mesh: Mesh,
    *,
    num_microbatches: int,
    data_axis: str = DATA_AXIS,
    stage_axis: str = STAGE_AXIS,
):
    """Wrap ``stage_fn`` in a microbatched GPipe schedule over ``mesh``.

    ``stage_fn(local_params, x) -> y`` applies one stage's resident layers to
    one microbatch (``y`` must have ``x``'s shape/dtype — a residual-block
    stack). Returns ``fn(stacked_params, x_mb)`` where ``stacked_params``
    leaves carry the leading layer axis (sharded over ``stage``) and
    ``x_mb`` is ``(M, rows, ...)`` (rows sharded over ``data``), computing
    the full ``S``-stage composition for every microbatch.
    """
    num_stages = mesh.shape[stage_axis]
    ticks = num_microbatches + num_stages - 1
    fwd_perm = [(i, i + 1) for i in range(num_stages - 1)]

    def local_schedule(layer_params, x_mb):
        s = jax.lax.axis_index(stage_axis)
        out = jnp.zeros(x_mb.shape, x_mb.dtype)
        state0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)

        def tick(carry, t):
            out, state = carry
            # stage 0 ingests microbatch t; later stages consume the
            # activation ppermuted from their predecessor last tick
            inject = x_mb[jnp.clip(t, 0, num_microbatches - 1)]
            x_in = jnp.where(s == 0, inject, state)
            y = stage_fn(layer_params, x_in)
            # the last stage finishes microbatch t - (S-1) at tick t
            mb = t - (num_stages - 1)
            mb_c = jnp.clip(mb, 0, num_microbatches - 1)
            valid = (s == num_stages - 1) & (mb >= 0)
            out = out.at[mb_c].set(jnp.where(valid, y, out[mb_c]))
            state = (
                jax.lax.ppermute(y, stage_axis, fwd_perm)
                if fwd_perm
                else y
            )
            return (out, state), None

        (out, _), _ = jax.lax.scan(
            tick, (out, state0), jnp.arange(ticks)
        )
        # only the last stage holds real outputs (others contributed zeros);
        # the psum makes the result stage-invariant so out_specs can
        # replicate it over the stage axis
        return jax.lax.psum(out, stage_axis)

    # checking off: the hand-rolled ppermute schedule carries no
    # varying-axes info the static checker (check_vma) can follow
    return shard_map_nocheck(
        local_schedule,
        mesh=mesh,
        in_specs=(P(stage_axis), P(None, data_axis)),
        out_specs=P(None, data_axis),
    )


class PipelinedTransformerLM:
    """dp x pp transformer LM: same params/numerics as
    :class:`..models.transformer.TransformerLM` (``scan_layers=True``), with
    the layer stack executed as a GPipe schedule.

    Drop-in for the Trainer together with :class:`PipelineParallel`::

        mesh = create_mesh({'data': D, 'stage': S})
        model = PipelinedTransformerLM(cfg, mesh, num_microbatches=4)
        strategy = PipelineParallel(mesh, num_microbatches=4)
        Trainer(model, loader, tx, strategy=strategy, loss='cross_entropy')

    Constraints: ``cfg.n_layers % S == 0``; per-step batch ``B`` must satisfy
    ``B % M == 0`` and ``(B / M) % D == 0``; dense FFN only (MoE's sown
    aux losses compose with expert parallelism, not the pipeline schedule).
    """

    def __init__(
        self,
        cfg,
        mesh: Mesh,
        *,
        num_microbatches: int,
        data_axis: str = DATA_AXIS,
        stage_axis: str = STAGE_AXIS,
    ):
        from pytorch_distributed_training_tutorials_tpu.models.transformer import (
            Block,
            TransformerLM,
        )

        if not cfg.scan_layers:
            import dataclasses

            cfg = dataclasses.replace(cfg, scan_layers=True)
        if cfg.moe_experts:
            raise ValueError(
                "PipelinedTransformerLM supports dense blocks only "
                "(MoE aux-loss sowing does not thread the pipeline scan)"
            )
        num_stages = mesh.shape[stage_axis]
        if cfg.n_layers % num_stages:
            raise ValueError(
                f"n_layers {cfg.n_layers} not divisible by "
                f"{num_stages} pipeline stages"
            )
        self.cfg = cfg
        self.mesh = mesh
        self.num_microbatches = num_microbatches
        self.inner = TransformerLM(cfg)
        block = Block(cfg)

        def stage_fn(layer_params, x):
            # layer_params leaves: (n_layers/S, ...) — this stage's block
            def body(x, p):
                return block.apply({"params": p}, x), None

            x, _ = jax.lax.scan(body, x, layer_params)
            return x

        self._pipeline = spmd_pipeline(
            stage_fn,
            mesh,
            num_microbatches=num_microbatches,
            data_axis=data_axis,
            stage_axis=stage_axis,
        )

    def init(self, key, tokens):
        return self.inner.init(key, tokens)

    def apply(self, variables, tokens):
        from pytorch_distributed_training_tutorials_tpu.models.transformer import (
            RMSNorm,
        )

        cfg = self.cfg
        params = variables["params"]
        m = self.num_microbatches
        b = tokens.shape[0]
        if b % m:
            raise ValueError(f"batch {b} not divisible by {m} microbatches")
        if tokens.shape[1] > cfg.max_seq_len:
            # same validation contract as the unpipelined TransformerLM
            raise ValueError(
                f"sequence length {tokens.shape[1]} exceeds "
                f"max_seq_len {cfg.max_seq_len}"
            )
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype).apply(
            {"params": params["tok_emb"]}, tokens
        )
        x_mb = x.reshape(m, b // m, *x.shape[1:])
        y_mb = self._pipeline(params["layers"]["block"], x_mb)
        y = y_mb.reshape(b, *x.shape[1:])
        y = RMSNorm(cfg.norm_eps).apply({"params": params["final_norm"]}, y)
        return nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype
        ).apply({"params": params["lm_head"]}, y)

    # Trainer calls model.apply(variables, x); __call__ for plain use
    __call__ = apply


class PipelineParallel:
    """dp x pp sharding strategy: stacked layer params over ``stage``,
    embeddings/head replicated, batches over ``data``.

    Drop-in for :class:`.data_parallel.DataParallel` in the Trainer. The
    optimizer state follows the same placement because optax moments mirror
    the param tree (their key paths contain the same ``layers`` segment).
    """

    def __init__(
        self,
        mesh: Mesh,
        *,
        num_microbatches: int = 1,
        data_axis: str = DATA_AXIS,
        stage_axis: str = STAGE_AXIS,
        layers_key: str = "layers",
    ):
        self.mesh = mesh
        self.num_microbatches = num_microbatches
        self.data_axis = data_axis
        self.stage_axis = stage_axis
        self.layers_key = layers_key
        self.batch_sharding = NamedSharding(mesh, P(data_axis))
        self._stage0 = NamedSharding(mesh, P(stage_axis))
        self._replicated = NamedSharding(mesh, P())

    @property
    def num_devices(self) -> int:
        return self.mesh.shape.get(self.data_axis, 1)

    @property
    def num_stages(self) -> int:
        return self.mesh.shape.get(self.stage_axis, 1)

    def _leaf_sharding(self, key_path) -> NamedSharding:
        in_stack = any(
            getattr(k, "key", None) == self.layers_key for k in key_path
        )
        return self._stage0 if in_stack else self._replicated

    def variable_shardings(self, abstract_variables):
        return jax.tree_util.tree_map_with_path(
            lambda kp, _: self._leaf_sharding(kp), abstract_variables
        )

    def shard_state(self, state):
        return jax.tree_util.tree_map_with_path(
            lambda kp, leaf: jax.device_put(leaf, self._leaf_sharding(kp)),
            state,
        )

    def shard_batch(self, batch):
        return jax.device_put(batch, self.batch_sharding)

"""Chip smoke: the Trainer and the ServeEngine on a TPU, at the 1b widths.

The quickest proof that the system still starts on the chip. One process,
through the entry points a user calls, weights and data made from
``--seed``; every phase prints one JSON line and the first failing phase
ends the run with a non-zero exit code:

- ``device``: fails at once unless JAX sees a TPU.
- ``train``: ``TransformerLM`` at vocab 32000 / d_model 2048 / 16 heads /
  d_ff 8192, bf16, sequence 2048, through ``train.Trainer`` over a
  ``ShardedLoader`` with the kernel path (flash attention, fused cross
  entropy, fused AdamW, remat, scanned layers). Depth is cut to fit one
  chip's 16 GB; every width is the preset's. Checked against the plain
  path (dense attention, optax cross entropy) on the same parameters and
  batch.
- ``serve``: all 16 layers in int8 through ``serve.ServeEngine`` with its
  default options, checked against ``models.generate``; then the paged
  engine with the Pallas page-walk kernel against the gather path.
- ``serve_state``: one request through a model with recurrent state
  (``mb_per_layer``: Phi-4-mini-flash-reasoning's widths, 8 of its 32
  layers: Mamba state, a window ring and one shared cache in a slot), its
  prompt longer than the attention window, against ``models.generate``.
- ``serve_parallel``: three requests through two slots of a model whose
  every block runs a Mamba-2 mixer beside attention (``mamba_n_heads``:
  Falcon-H1-34B's widths, 2 of its 72 layers: a state of 4 MB and a KV
  cache in one layer), so that a slot is refilled; ``ssd_update`` and
  ``decode_attention`` on the carried stacks, against ``models.generate``.

``--chips 4`` runs, and runs only, the multi-chip phase: the ``train``
model on device 0 alone, under ``DataParallel`` on ``create_mesh()`` and
under ``FSDP``, same seed and global batch, losses compared.

``--rehearse`` runs the same control flow at toy widths and lets the CPU
pass the ``device`` phase (Pallas in interpret mode) — the tier-1 test's
path. Its last line names the platform it really ran on.

The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

# the 1b preset's widths (examples/serve_llm_int8.py presets()["1b"])
REAL = dict(
    vocab=32000, d_model=2048, n_heads=16, d_ff=8192, n_layers=16,
    seq=2048, train_layers=4, batch=4, steps=6, window=512,
    warm_lens=[16, 32, 64, 128, 256],
    prompt_lens=[16, 24, 40, 64, 96, 128, 160, 200, 256, 33, 77, 250],
    new_tokens=32, page_size=64, pool_pages=64,
    # Phi-4-mini-flash-reasoning's widths at a toy depth (8 of 32 layers:
    # two periods of Mamba and window layers, the Mamba and the full layer
    # between, one period of Gated Memory Unit and cross-attention)
    state=dict(d_model=2560, n_heads=40, n_kv_heads=20, d_ff=10240,
               n_layers=8, window=1024, ring=512, prompt_len=600),
    # Falcon-H1-34B-Instruct's widths at a toy depth (2 of 72 layers)
    parallel=dict(d_model=5120, n_heads=20, n_kv_heads=4, d_head=128,
                  d_ff=21504, n_layers=2, mamba_n_heads=32, mamba_d_head=128,
                  mamba_n_groups=2, mamba_d_state=256, mamba_chunk_size=128,
                  window=1024, prompt_lens=[600, 130, 333]),
)
TOY = dict(
    vocab=256, d_model=64, n_heads=4, d_ff=128, n_layers=2,
    seq=64, train_layers=2, batch=4, steps=5, window=64,
    warm_lens=[8, 16, 32], prompt_lens=[8, 12, 20, 32, 9, 31],
    new_tokens=8, page_size=8, pool_pages=32,
    state=dict(d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, n_layers=8,
               window=64, ring=8, prompt_len=20),
    parallel=dict(d_model=96, n_heads=4, n_kv_heads=2, d_head=16, d_ff=128,
                  n_layers=2, mamba_n_heads=4, mamba_d_head=16,
                  mamba_n_groups=2, mamba_d_state=8, mamba_chunk_size=8,
                  window=64, prompt_lens=[20, 9, 31]),
)
DEPTH_CUT = (
    "one chip's 16 GB cannot hold 16 layers of f32 parameters with two "
    "AdamW moments (1.2 B x 12 bytes before gradients and activations); "
    "every width kept, depth only cut"
)
LEARNING_RATE = 1e-4  # no warm-up: 3e-4 makes the first losses swing
TOL_LN_VOCAB = 0.75  # first loss vs ln(vocab): an untrained model
TOL_FUSED_VS_PLAIN = 0.05  # bf16 loss, kernel path vs plain path
# per-leaf ||g_kernel - g_plain|| / ||g_plain||, bf16 forward and backward
TOL_GRAD_REL = 0.1
TOL_UPDATE_REL = 0.01  # per-leaf, f32 both ways
# Sharded strategies vs one device: the Trainer's plain path reports its
# loss in bf16, whose steps are 0.0625 wide near ln(32000) — two of them.
TOL_MULTICHIP = 0.125
# Two greedy decodes of one model may part at a near-tie. Where they do,
# both tokens' reference logits must lie this close, relative to the
# largest |logit| of that position (the TPU's default f32 matmul is a
# bf16 pass, so two correct paths differ by a few percent there).
TOL_GREEDY_GAP = 0.1


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


def finish_phase(line: dict, failed: list[str]) -> None:
    """Print the phase's line; a failed check ends the run here."""
    line["ok"] = not failed
    line["failed"] = failed
    emit(**line)
    if failed:
        sys.exit(1)


class CompileLog:
    """Counts program builds (``ContractSentry``, the repo's compile
    probe) and persistent-cache hits since the last :meth:`take`."""

    def __init__(self):
        from jax import monitoring

        from pytorch_distributed_training_tutorials_tpu.obs.sentry import (
            ContractSentry,
        )

        self.sentry = ContractSentry().install()
        self.cache_hits = 0
        monitoring.register_event_listener(self._on_event)
        self._mark = (0, 0.0, 0)

    def _on_event(self, event: str, **kw) -> None:
        if event.endswith("/cache_hits"):
            self.cache_hits += 1

    def take(self) -> dict:
        """Builds, their seconds and cache hits since the last call."""
        now = (
            self.sentry.n_compiles, self.sentry.compile_ms_total,
            self.cache_hits,
        )
        n, ms, hits = (a - b for a, b in zip(now, self._mark))
        self._mark = now
        return {"n_compiles": n, "compile_s": ms / 1e3, "cache_hits": hits}


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# -- device ----------------------------------------------------------------


def phase_device(args, cache_dir: str) -> dict:
    import jax

    from pytorch_distributed_training_tutorials_tpu.data import native
    from pytorch_distributed_training_tutorials_tpu.parallel import (
        distributed,
    )

    # one host, one process: init() must see nothing to rendezvous with
    # (it runs before the first device query, as a launcher would call it)
    t0 = time.perf_counter()
    distributed.init()
    init_s = time.perf_counter() - t0
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    failed = []
    if dev.platform != "tpu" and not args.rehearse:
        failed.append("no TPU: jax.devices()[0].platform is not 'tpu'")
    if device["count"] != args.chips and not args.rehearse:
        failed.append(f"--chips {args.chips} but {device['count']} devices")
    if jax.process_count() != 1:
        failed.append("more than one process")
    if not native.native_available():
        failed.append("data/native.py did not build csrc/fastgather.cpp")
    finish_phase(
        {
            "phase": "device", **device, "jax": jax.__version__,
            "rehearse": args.rehearse,
            "pallas_interpret": jax.default_backend() != "tpu",
            "compile_cache_dir": cache_dir,
            "compile_cache_from_env": bool(
                os.environ.get("JAX_COMPILATION_CACHE_DIR")
            ),
            "native_gather": native.native_available(),
            "tpu_env": sorted(k for k in os.environ if k.startswith("TPU_")),
            "distributed_init_s": init_s,
            "process_count": jax.process_count(),
        },
        failed,
    )
    return device


# -- train -----------------------------------------------------------------


def train_config(w: dict, kernels: bool = True):
    """The train model: preset widths, cut depth, bf16, remat, scanned
    layers; ``kernels`` picks flash attention over dense attention."""
    import jax.numpy as jnp

    from pytorch_distributed_training_tutorials_tpu.models import (
        TransformerConfig,
    )
    from pytorch_distributed_training_tutorials_tpu.ops.flash_attention import (
        flash_attention,
    )

    return TransformerConfig(
        vocab_size=w["vocab"], d_model=w["d_model"], n_heads=w["n_heads"],
        d_ff=w["d_ff"], n_layers=w["train_layers"], max_seq_len=w["seq"],
        dtype=jnp.bfloat16, scan_layers=True, remat=True,
        remat_policy="dots",
        attention_fn=flash_attention if kernels else None,
    )


KERNEL_PATH = (
    "flash_attention + fused_cross_entropy + fused_adamw, remat=dots, "
    "scan_layers"
)
PLAIN_PATH = (
    "dense causal_attention + optax cross entropy + optax.adamw, "
    "remat=dots, scan_layers"
)


def token_dataset(w: dict, seed: int):
    """``steps`` global batches of next-token pairs from ``seed``. Token
    ids are skewed to the low end, so there is a unigram to learn and the
    loss can fall below the untrained model's ln(vocab)."""
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.data import ArrayDataset

    rng = np.random.Generator(np.random.PCG64(seed))
    u = rng.random((w["batch"] * w["steps"], w["seq"] + 1))
    toks = (w["vocab"] * u**4).astype(np.int32)
    return ArrayDataset((toks[:, :-1], np.ascontiguousarray(toks[:, 1:])))


def run_trainer(
    w: dict, seed: int, strategy, log: CompileLog, kernels: bool = True
) -> dict:
    """One epoch of ``steps`` optimizer steps through ``Trainer`` under
    ``strategy``, on the kernel path or the plain one; returns losses,
    timings and the trainer itself."""
    import jax
    import optax

    from pytorch_distributed_training_tutorials_tpu.data import ShardedLoader
    from pytorch_distributed_training_tutorials_tpu.models import (
        TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.ops.fused_optim import (
        fused_adamw,
    )
    from pytorch_distributed_training_tutorials_tpu.train import Trainer

    loader = ShardedLoader(
        token_dataset(w, seed), w["batch"], strategy.mesh,
        batch_mode="global", seed=seed,
    )
    losses, stamps = [], []
    steady = {}

    def on_step(step, loss):
        # a smoke wants honest per-step times: block on every step
        losses.append(float(loss))
        stamps.append(time.perf_counter())
        if step == 1:
            steady.update(log.take())

    log.take()
    adamw = fused_adamw if kernels else optax.adamw
    trainer = Trainer(
        TransformerLM(train_config(w, kernels)), loader,
        adamw(LEARNING_RATE, weight_decay=0.01), strategy=strategy,
        loss="fused_cross_entropy" if kernels else "cross_entropy",
        seed=seed, quiet=True, on_step=on_step,
    )
    # the batch step 1 will see, for the plain-path comparison and the
    # split check (the loader's order is a function of seed and epoch)
    loader.set_epoch(0)
    first_batch = next(iter(loader))
    t0 = time.perf_counter()
    trainer.train(1)
    after = log.take()
    return {
        "trainer": trainer, "first_batch": first_batch, "losses": losses,
        "first_step_s": stamps[0] - t0,
        "step_s": [b - a for a, b in zip(stamps, stamps[1:])],
        "first_step_builds": steady, "later_builds": after,
        "n_params": sum(
            x.size for x in jax.tree_util.tree_leaves(trainer.state.params)
        ),
    }


def kernel_vs_plain(w: dict, strategy, seed: int, batch) -> dict:
    """The first step both ways, on the parameters the Trainer started from
    (same seed, same init program): objective and gradients of the kernel
    path (the Trainer's own loss definition) against the plain one — dense
    ``causal_attention`` and optax cross entropy over materialized logits
    — then one ``fused_adamw`` update against ``optax.adamw``'s."""
    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_distributed_training_tutorials_tpu.models import (
        TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.ops.fused_optim import (
        fused_adamw,
    )
    from pytorch_distributed_training_tutorials_tpu.train.trainer import (
        _make_loss_fn, create_train_state,
    )

    def loss_and_grads(kernels: bool):
        model = TransformerLM(train_config(w, kernels))
        state = create_train_state(
            model, optax.identity(), batch[0], strategy=strategy, seed=seed
        )
        if kernels:  # the Trainer's own objective
            fused = _make_loss_fn(
                "fused_cross_entropy", has_batch_stats=False,
                aux_loss_weight=0.0,
            )

            def loss(params):
                return fused(params, state, batch)[0]
        else:  # f32 logits: the reference should not round at bf16

            def loss(params):
                logits = model.apply({"params": params}, batch[0])
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), batch[1]
                ).mean()

        return state.params, *jax.jit(jax.value_and_grad(loss))(state.params)

    params, loss_p, grads_p = loss_and_grads(kernels=False)
    _, loss_k, grads_k = loss_and_grads(kernels=True)

    @jax.jit
    def rel_err(gk, gp):
        gk, gp = gk.astype(jnp.float32), gp.astype(jnp.float32)
        return jnp.linalg.norm(gk - gp) / jnp.linalg.norm(gp)

    def worst(tree_k, tree_p):
        errs = jax.tree_util.tree_map(rel_err, tree_k, tree_p)
        path, err = max(
            jax.tree_util.tree_leaves_with_path(errs),
            key=lambda kv: float(kv[1]),
        )
        return float(err), jax.tree_util.keystr(path)

    grad_err, grad_leaf = worst(grads_k, grads_p)
    del grads_k

    # the third kernel: one AdamW update of the same gradients both ways
    def first_update(adamw):
        tx = adamw(LEARNING_RATE, weight_decay=0.01)
        return jax.jit(
            lambda g, p: tx.update(g, tx.init(p), p)[0]
        )(grads_p, params)

    upd_err, upd_leaf = worst(
        first_update(fused_adamw), first_update(optax.adamw)
    )
    return {
        "plain_loss0": float(loss_p), "kernel_loss0": float(loss_k),
        "grad_rel_err_max": grad_err, "grad_rel_err_max_leaf": grad_leaf,
        "adamw_update_rel_err_max": upd_err,
        "adamw_update_rel_err_max_leaf": upd_leaf,
    }


def loss_checks(
    w: dict, losses: list[float], must_fall: bool = True
) -> list[str]:
    failed = []
    if len(losses) < w["steps"]:
        failed.append(f"only {len(losses)} optimizer steps")
    if not all(math.isfinite(x) for x in losses):
        failed.append("non-finite loss")
    elif abs(losses[0] - math.log(w["vocab"])) > TOL_LN_VOCAB:
        failed.append("first loss far from ln(vocab)")
    elif must_fall and not losses[-1] < losses[0]:
        failed.append("loss did not fall")
    return failed


def model_line(w: dict) -> dict:
    return {k: w[k] for k in ("vocab", "d_model", "n_heads", "d_ff")}


def phase_train(args, w: dict, log: CompileLog) -> None:
    import jax

    from pytorch_distributed_training_tutorials_tpu.parallel import (
        DataParallel,
    )

    strategy = DataParallel()
    run = run_trainer(w, args.seed, strategy, log)
    failed = loss_checks(w, run["losses"])
    if run["later_builds"]["n_compiles"]:
        failed.append("a program was built after the first step")
    peak = peak_bytes(jax.devices()[0])
    first_batch = run.pop("first_batch")
    del run["trainer"]
    gc.collect()
    ref = kernel_vs_plain(w, strategy, args.seed, first_batch)
    diff = abs(run["losses"][0] - ref["plain_loss0"])
    if not diff <= TOL_FUSED_VS_PLAIN:
        failed.append("first loss differs from the plain path")
    if not ref["grad_rel_err_max"] <= TOL_GRAD_REL:
        failed.append("first gradients differ from the plain path")
    if not ref["adamw_update_rel_err_max"] <= TOL_UPDATE_REL:
        failed.append("fused_adamw's first update differs from optax.adamw")
    finish_phase(
        {
            "phase": "train", "model": model_line(w),
            "n_layers": w["train_layers"],
            "depth_cut": f"{w['train_layers']} of {w['n_layers']} layers: "
            + DEPTH_CUT,
            "n_params": run["n_params"], "batch": w["batch"],
            "seq": w["seq"], "dtype": "bfloat16", "path": KERNEL_PATH,
            "pallas_interpret": jax.default_backend() != "tpu",
            "optimizer_steps": len(run["losses"]), "losses": run["losses"],
            "ln_vocab": math.log(w["vocab"]),
            "tol_first_vs_ln_vocab": TOL_LN_VOCAB,
            **ref, "fused_vs_plain_abs": diff,
            "tol_fused_vs_plain_abs": TOL_FUSED_VS_PLAIN,
            "tol_grad_rel_err": TOL_GRAD_REL,
            "tol_adamw_update_rel_err": TOL_UPDATE_REL,
            "first_step_s": run["first_step_s"], "step_s": run["step_s"],
            "builds_to_first_step": run["first_step_builds"],
            "builds_after_first_step": run["later_builds"]["n_compiles"],
            "peak_bytes_in_use": peak,
        },
        failed,
    )


# -- serve -----------------------------------------------------------------


def serve_model(w: dict, seed: int):
    """All ``n_layers`` of the preset, int8: f32 parameters made on the
    device from ``seed``, then quantized there (the example's
    ``quantize`` + scanned-layers serving layout, minus its checkpoint)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tutorials_tpu.models import (
        TransformerConfig, TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        quantize_lm_params,
    )

    cfg = TransformerConfig(
        vocab_size=w["vocab"], d_model=w["d_model"], n_heads=w["n_heads"],
        d_ff=w["d_ff"], n_layers=w["n_layers"], max_seq_len=w["window"],
        scan_layers=True,
    )
    params = jax.jit(TransformerLM(cfg).init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    params = jax.jit(quantize_lm_params, donate_argnums=0)(params)
    jax.block_until_ready(params)
    return TransformerLM(dataclasses.replace(cfg, quantized=True)), params


def prompts_for(w: dict, lens: list[int], seed: int) -> list[list[int]]:
    import numpy as np

    rng = np.random.Generator(np.random.PCG64(seed))
    return [rng.integers(0, w["vocab"], n).tolist() for n in lens]


def serve_requests(engine, prompts, new_tokens: int):
    """Submit, drain, and return completions in submission order."""
    from pytorch_distributed_training_tutorials_tpu.serve import Request

    ids = [
        engine.submit(Request(prompt=p, max_new_tokens=new_tokens))
        for p in prompts
    ]
    done = {c.request_id: c for c in engine.run_until_idle()}
    return [done[i] for i in ids]


def run_engine(w: dict, lm, params, seed: int, log: CompileLog, **options):
    """Warm an engine over every prompt bucket, then serve the measured
    requests; returns (line, failed, completions)."""
    from pytorch_distributed_training_tutorials_tpu.serve import ServeEngine

    log.take()
    engine = ServeEngine(lm, params, **options)
    t0 = time.perf_counter()
    serve_requests(
        engine, prompts_for(w, w["warm_lens"], seed + 1), w["new_tokens"]
    )
    warm_s = time.perf_counter() - t0
    warm = log.take()
    prompts = prompts_for(w, w["prompt_lens"], seed + 2)
    t0 = time.perf_counter()
    done = serve_requests(engine, prompts, w["new_tokens"])
    wall_s = time.perf_counter() - t0
    steady = log.take()
    reasons = sorted({c.finish_reason for c in done})
    errors = engine.fault_stats()["prefill_errors"]
    failed = []
    if len(done) != len(prompts):
        failed.append("a request did not complete")
    if not set(reasons) <= {"length", "eos"}:
        failed.append(f"finish reasons {reasons}")
    if errors:
        failed.append(f"{errors} prefill errors")
    if steady["n_compiles"]:
        failed.append("a program was built after warm-up")
    line = {
        "options": options or "defaults", "requests": len(prompts),
        "prompt_lens": w["prompt_lens"], "new_tokens": w["new_tokens"],
        "warmup_requests": len(w["warm_lens"]), "warmup_s": warm_s,
        "warmup_builds": warm, "builds_after_warmup": steady["n_compiles"],
        "completions": len(done),
        "tokens_completed": sum(len(c.tokens) for c in done),
        "wall_s": wall_s, "finish_reasons": reasons,
        "n_prefill_errors": errors,
    }
    return line, failed, done


class GreedyJudge:
    """Where two greedy decodes of one prompt part, how far apart are the
    two tokens under the reference forward (the model's plain full-window
    apply: no cache, no engine)? 0.0 when they never part."""

    def __init__(self, w: dict, lm, params):
        import jax
        import jax.numpy as jnp

        self.window = w["window"]
        self.params = params

        @jax.jit
        def logits_at(params, tokens, pos):
            return lm.apply({"params": params}, tokens)[0, pos].astype(
                jnp.float32
            )

        self._logits_at = logits_at

    def gap(self, prompt: list[int], a: list[int], b: list[int]) -> float:
        import numpy as np

        j = next(
            (i for i, (x, y) in enumerate(zip(a, b)) if x != y), None
        )
        if j is None:
            return 0.0 if len(a) == len(b) else float("inf")
        seq = prompt + a[:j]
        tokens = np.zeros((1, self.window), np.int32)
        tokens[0, : len(seq)] = seq
        logits = np.asarray(
            self._logits_at(self.params, tokens, len(seq) - 1)
        )
        return float(abs(logits[a[j]] - logits[b[j]]) / np.abs(logits).max())


def phase_serve(args, w: dict, log: CompileLog) -> None:
    import jax
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.models.generate import (
        generate,
    )

    t0 = time.perf_counter()
    lm, params = serve_model(w, args.seed)
    setup_s = time.perf_counter() - t0
    judge = GreedyJudge(w, lm, params)
    common = {
        "model": model_line(w), "n_layers": w["n_layers"],
        "weights": "int8, made on the device from --seed",
        "window": w["window"],
        "pallas_interpret": jax.default_backend() != "tpu",
        "tol_greedy_gap_rel": TOL_GREEDY_GAP,
    }

    # default engine, against models.generate on one prompt
    line, failed, done = run_engine(w, lm, params, args.seed, log)
    probe = done[2]
    ref = np.asarray(
        generate(
            lm, params, np.asarray([probe.prompt], np.int32),
            w["new_tokens"],
        )
    )[0, len(probe.prompt):].tolist()
    gap = judge.gap(probe.prompt, probe.tokens, ref)
    if not gap <= TOL_GREEDY_GAP:
        failed.append("greedy decode differs from models.generate")
    finish_phase(
        {
            "phase": "serve", **common, "weights_setup_s": setup_s, **line,
            "generate_tokens_equal": probe.tokens == ref,
            "generate_greedy_gap_rel": gap,
            "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
        },
        failed,
    )

    # paged engine: the Pallas page-walk kernel against the gather path
    paged = dict(
        paged=True, page_size=w["page_size"], pool_pages=w["pool_pages"]
    )
    g_line, g_failed, g_done = run_engine(
        w, lm, params, args.seed, log, **paged, paged_kernel=False
    )
    finish_phase({"phase": "serve_paged_gather", **common, **g_line}, g_failed)
    k_line, k_failed, k_done = run_engine(
        w, lm, params, args.seed, log, **paged, paged_kernel=True
    )
    gaps = [
        judge.gap(k.prompt, k.tokens, g.tokens)
        for k, g in zip(k_done, g_done)
    ]
    if not max(gaps) <= TOL_GREEDY_GAP:
        k_failed.append("kernel decode differs from the gather path")
    finish_phase(
        {
            "phase": "serve_paged_kernel", **common, **k_line,
            "requests_token_equal_to_gather": sum(
                k.tokens == g.tokens for k, g in zip(k_done, g_done)
            ),
            "greedy_gap_rel_max": max(gaps),
            "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
        },
        k_failed,
    )


def phase_serve_state(args, w: dict, log: CompileLog) -> None:
    """One request through a model with recurrent state (``mb_per_layer``:
    Mamba state, a window ring and one shared cache in a slot), int8, its
    prompt longer than the attention window: ``ServeEngine`` against
    ``models.generate``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.models import (
        TransformerConfig, TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.models.generate import (
        generate,
    )
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        quantize_lm_params,
    )
    from pytorch_distributed_training_tutorials_tpu.serve import ServeEngine

    st = w["state"]
    cfg = TransformerConfig(
        vocab_size=w["vocab"], d_model=st["d_model"], n_heads=st["n_heads"],
        n_kv_heads=st["n_kv_heads"], d_ff=st["d_ff"], n_layers=st["n_layers"],
        max_seq_len=st["window"], norm_eps=1e-5, mb_per_layer=2,
        sliding_window=st["ring"], tie_embeddings=True, scan_layers=True,
        dtype=jnp.bfloat16,
    )
    params = jax.jit(TransformerLM(cfg).init)(
        jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    params = jax.jit(
        lambda p: quantize_lm_params(p, jnp.bfloat16), donate_argnums=0
    )(params)
    lm = TransformerLM(dataclasses.replace(cfg, quantized=True))
    log.take()
    engine = ServeEngine(lm, params, n_slots=2, tokens_per_launch=8)
    prompt = prompts_for(w, [st["prompt_len"]], args.seed + 3)
    t0 = time.perf_counter()
    done = serve_requests(engine, prompt, w["new_tokens"])
    wall_s = time.perf_counter() - t0
    ref = np.asarray(
        generate(lm, params, np.asarray(prompt, np.int32), w["new_tokens"])
    )[0, st["prompt_len"]:].tolist()
    gap = GreedyJudge({"window": st["window"]}, lm, params).gap(
        prompt[0], done[0].tokens, ref
    )
    failed = []
    if done[0].finish_reason != "length":
        failed.append(f"finish reason {done[0].finish_reason}")
    if not gap <= TOL_GREEDY_GAP:
        failed.append("greedy decode differs from models.generate")
    finish_phase(
        {
            "phase": "serve_state", "model": dict(st, vocab=w["vocab"]),
            "weights": "int8, made on the device from --seed",
            "prompt_len": st["prompt_len"], "new_tokens": w["new_tokens"],
            "wall_s_with_builds": wall_s, "builds": log.take(),
            "generate_tokens_equal": done[0].tokens == ref,
            "generate_greedy_gap_rel": gap, **engine.stats("slot"),
            "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
        },
        failed,
    )


def phase_serve_parallel(args, w: dict, log: CompileLog) -> None:
    """Three requests through two slots of a model with a Mamba-2 mixer
    beside attention in every block (``mamba_n_heads``: a state and a KV
    cache in one layer, the published multipliers' places taken), int8:
    ``ServeEngine`` against ``models.generate``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.models import (
        TransformerConfig, TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.models.generate import (
        generate,
    )
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        quantize_lm_params,
    )
    from pytorch_distributed_training_tutorials_tpu.serve import ServeEngine

    pm = dict(w["parallel"])
    window, lens = pm.pop("window"), pm.pop("prompt_lens")
    cfg = TransformerConfig(
        vocab_size=w["vocab"], max_seq_len=window, norm_eps=1e-5,
        scan_layers=True, dtype=jnp.bfloat16, embedding_multiplier=2.0,
        key_multiplier=0.5, attention_out_multiplier=0.5,
        ssm_in_multiplier=0.5, ssm_out_multiplier=0.5,
        ssm_multipliers=(0.5, 1.0, 0.5, 1.0, 0.5),
        mlp_multipliers=(0.5, 0.25), **pm,
    )
    params = jax.jit(TransformerLM(cfg).init)(
        jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    params = jax.jit(quantize_lm_params, donate_argnums=0)(params)
    lm = TransformerLM(dataclasses.replace(cfg, quantized=True))
    log.take()
    engine = ServeEngine(lm, params, n_slots=2, tokens_per_launch=8)
    prompts = prompts_for(w, lens, args.seed + 4)
    t0 = time.perf_counter()
    done = serve_requests(engine, prompts, w["new_tokens"])
    wall_s = time.perf_counter() - t0
    judge = GreedyJudge({"window": window}, lm, params)
    gaps, equal = [], []
    for prompt, c in zip(prompts, done):
        ref = np.asarray(generate(
            lm, params, np.asarray([prompt], np.int32), w["new_tokens"]
        ))[0, len(prompt):].tolist()
        gaps.append(judge.gap(prompt, c.tokens, ref))
        equal.append(c.tokens == ref)
    failed = []
    if any(c.finish_reason != "length" for c in done):
        failed.append(f"finish reasons {[c.finish_reason for c in done]}")
    if not max(gaps) <= TOL_GREEDY_GAP:
        failed.append("greedy decode differs from models.generate")
    finish_phase(
        {
            "phase": "serve_parallel", "model": dict(pm, vocab=w["vocab"]),
            "weights": "int8, made on the device from --seed",
            "prompt_lens": lens, "new_tokens": w["new_tokens"],
            "wall_s_with_builds": wall_s, "builds": log.take(),
            "generate_tokens_equal": equal,
            "generate_greedy_gap_rel": max(gaps), **engine.stats("slot"),
            "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
        },
        failed,
    )


# -- four chips ------------------------------------------------------------


def phase_multichip(args, w: dict, log: CompileLog) -> None:
    """The train phase's model, seed and global batch three ways: device 0
    alone, DataParallel over all chips, FSDP over all chips. FSDP is the
    strategy the README presents for a model one chip cannot hold on the
    mesh ``create_mesh()`` gives (``{'data': N}``): a drop-in for
    DataParallel in the Trainer, where TensorParallel needs a ``model``
    axis and rules per architecture.

    All three arms run the PLAIN path. The chip's compiler refuses the
    kernel path under a mesh of more than one device ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map"): ``flash_attention``, ``fused_cross_entropy`` and
    ``fused_adamw`` are bare ``pallas_call``s that no strategy wraps."""
    import jax

    from pytorch_distributed_training_tutorials_tpu import create_mesh
    from pytorch_distributed_training_tutorials_tpu.ops import (
        per_shard_shapes,
    )
    from pytorch_distributed_training_tutorials_tpu.parallel import (
        FSDP, DataParallel,
    )

    devices = jax.devices()
    n = len(devices)
    w = dict(w, steps=max(3, w["steps"] // 2))
    arms = {
        "one_device": DataParallel(create_mesh(devices=devices[:1])),
        "data_parallel": DataParallel(create_mesh()),
        "fsdp": FSDP(create_mesh()),
    }
    lines, failed = {}, []
    for name, strategy in arms.items():
        run = run_trainer(w, args.seed, strategy, log, kernels=False)
        trainer = run.pop("trainer")
        batch = run.pop("first_batch")
        leaves = jax.tree_util.tree_leaves(trainer.state.params)
        tree_bytes = sum(l.nbytes for l in leaves)
        per_device = [
            sum(
                s.data.nbytes
                for l in leaves for s in l.addressable_shards
                if s.device == d
            )
            for d in devices
        ]
        line = {
            "mesh": dict(strategy.mesh.shape), "losses": run["losses"],
            "first_step_s": run["first_step_s"], "step_s": run["step_s"],
            "builds_to_first_step": run["first_step_builds"],
            "builds_after_first_step": run["later_builds"]["n_compiles"],
            "batch_shard_shapes": [
                list(s) for s in per_shard_shapes(batch[0])
            ],
            "param_tree_bytes": tree_bytes,
            "param_bytes_per_device": per_device,
            "bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use") for d in devices
            ],
        }
        if name == "one_device":
            # three steps of a bf16-rounded loss: learning is the
            # one-chip train phase's check, not this one's
            failed += loss_checks(w, run["losses"], must_fall=False)
        else:
            diffs = [
                abs(a - b)
                for a, b in zip(run["losses"], lines["one_device"]["losses"])
            ]
            line["loss_abs_diff_vs_one_device"] = diffs
            if len(diffs) < 3 or not max(diffs) <= TOL_MULTICHIP:
                failed.append(f"{name}: losses leave the one-device run")
            want = [w["batch"] // n, w["seq"]]
            if line["batch_shard_shapes"] != [want] * n:
                failed.append(f"{name}: batch not split {n} ways")
            if jax.default_backend() == "tpu" and not all(
                line["bytes_in_use"]
            ):
                failed.append(f"{name}: a device holds nothing")
        if name == "data_parallel":
            hlo = trainer.train_step.lower(
                trainer.state, batch
            ).compile().as_text()
            line["all_reduce_in_step"] = "all-reduce" in hlo
            if not line["all_reduce_in_step"]:
                failed.append("data_parallel: no all-reduce in the step")
        if name == "fsdp" and max(per_device) >= tree_bytes:
            failed.append("fsdp: a device holds the whole parameter tree")
        lines[name] = line
        del trainer, leaves
        gc.collect()
    finish_phase(
        {
            "phase": "multichip", "model": model_line(w),
            "n_layers": w["train_layers"], "batch": w["batch"],
            "seq": w["seq"], "dtype": "bfloat16", "path": PLAIN_PATH,
            "kernel_path": "refused by the compiler under a multi-device "
            "mesh: Mosaic kernels cannot be automatically partitioned",
            "tol_loss_abs": TOL_MULTICHIP, **lines,
        },
        failed,
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the multi-chip phase, on four chips",
    )
    ap.add_argument(
        "--rehearse", action="store_true",
        help="toy widths, CPU allowed: the control flow, not a chip run",
    )
    args = ap.parse_args()
    if args.rehearse and args.chips == 4:
        # four virtual CPU devices, set before jax picks its backend
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
        )

    from pytorch_distributed_training_tutorials_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    t0 = time.perf_counter()
    device = phase_device(args, cache_dir)
    w = TOY if args.rehearse else REAL
    log = CompileLog()
    if args.chips == 4:
        phase_multichip(args, w, log)
    else:
        phase_train(args, w, log)
        phase_serve(args, w, log)
        phase_serve_state(args, w, log)
        phase_serve_parallel(args, w, log)
    emit(phase="total", ok=True, wall_s=time.perf_counter() - t0)
    emit(ok=True, device=device)


if __name__ == "__main__":
    main()

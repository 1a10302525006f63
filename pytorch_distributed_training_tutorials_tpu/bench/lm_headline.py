"""LM train-step MFU: the transformer training headline (bench leg).

The ResNet headline (bench.py) is a convolution workload; the standard
figure of merit for a distributed-training framework is what fraction of
peak a TRANSFORMER train step achieves. This module owns that measurement
— model/batch/attention/remat configuration, the one-launch lax.scan
chain timing (one launch + one closing fetch per chain), the
PaLM-convention model-FLOPs numerator — and a CLI that runs the tuned
winner and emits a one-line JSON receipt. The peak it divides by comes
from :data:`PEAK_BF16_BY_DEVICE_KIND`: a device that is not in the table
raises, so a CPU run can never print a chip's utilization.

Round-5 tuning (last measured on one v5e chip in round 5, before PRs 1-20;
TRAIN_LLM_r05.json):

- Pallas flash attention >> dense at S=2048 (41.5%% vs 24.9%% MFU at the
  350m scan point) — dense materializes (B, H, S, S) score temps.
- remat is the ENABLER, not a tax: without it a 350m/B=8 step wants
  32.5 GiB of activations (15.75 available); remat_policy="dots"
  (save projection/FFN matmul outputs, recompute elementwise+attention)
  beats full recompute by ~3 MFU points.
- UNROLLED layers beat nn.scan for TRAINING here: the scan's stacked
  activation saves are dynamic-update-slice fusions in awkward layouts —
  ~21%% of device time in the 350m trace — and cost MORE memory
  (15.6 vs 10.9 GiB at the same point). Serving keeps scan_layers (its
  constraint is program size).
- Winner on one v5e lite chip: 760m preset (1.01B params), B=2,
  flash(1024,1024), remat="dots", unrolled, 12-step chain ->
  52.1%% MFU wall (53.9%% device-rate), 15.5k tok/s.

``--fused`` runs a second arm with the memory-bound tail fused away —
logits-free blockwise cross entropy (ops.fused_loss; the (B, S, V) logits
never materialize) plus single-pass fused AdamW (ops.fused_optim) — and
emits BOTH arms in the JSON ({"baseline": ..., "fused": ...}): the receipt
for what the fused tail buys at fixed HBM.

Run:  python -m pytorch_distributed_training_tutorials_tpu.bench.lm_headline [--json out.json]
Sweep CLI with the full tuning grid: scripts/train_llm_mfu.py.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

# Peak bf16 FLOP/s of one chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).
PEAK_BF16_BY_DEVICE_KIND = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
}


def peak_bf16() -> float:
    """The attached device's bf16 peak; a device that is not in the table
    is an error, not a default — utilization of an unknown (or CPU)
    device is not a number this module may print."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_BY_DEVICE_KIND:
        raise RuntimeError(
            f"no bf16 peak on record for device_kind {kind!r} "
            f"(known: {sorted(PEAK_BF16_BY_DEVICE_KIND)}): MFU is defined "
            "against a chip's published peak — add the device with its "
            "source to PEAK_BF16_BY_DEVICE_KIND"
        )
    return PEAK_BF16_BY_DEVICE_KIND[kind]

PRESETS = {
    # name: (d_model, n_layers, n_heads, vocab)
    "smoke": (64, 2, 4, 256),
    "125m": (768, 12, 12, 32768),
    "350m": (1024, 24, 16, 32768),
    "760m": (1536, 24, 16, 32768),
}


def build(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tutorials_tpu.models import (
        TransformerConfig, TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.ops.flash_attention import (
        make_flash_attention,
    )
    from pytorch_distributed_training_tutorials_tpu.train.trainer import (
        TrainState, _train_step_fn,
    )

    d_model, n_layers, n_heads, vocab = PRESETS[args.preset]
    attention_fn = None
    if args.attn == "flash":
        attention_fn = make_flash_attention(args.block_q, args.block_k)
    cfg = TransformerConfig(
        vocab_size=vocab,
        d_model=d_model,
        n_layers=n_layers,
        n_heads=n_heads,
        max_seq_len=args.seq,
        dtype=jnp.bfloat16,
        scan_layers=not args.no_scan,
        remat=args.remat,
        remat_policy=args.remat_policy,
        attention_fn=attention_fn,
    )
    model = TransformerLM(cfg)
    key = jax.random.PRNGKey(0)
    params = jax.jit(model.init)(key, jnp.zeros((1, args.seq), jnp.int32))[
        "params"
    ]
    fused = getattr(args, "fused", False)
    if fused:
        from pytorch_distributed_training_tutorials_tpu.ops.fused_optim import (
            fused_adamw,
        )

        tx = fused_adamw(3e-4, weight_decay=0.01)
    else:
        tx = optax.adamw(3e-4, weight_decay=0.01)
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    rng = np.random.Generator(np.random.PCG64(0))
    toks = jnp.asarray(
        rng.integers(0, vocab, (args.batch, args.seq + 1)), jnp.int32
    )
    batch = (toks[:, :-1], toks[:, 1:])
    step_fn = _train_step_fn(
        "fused_cross_entropy" if fused else "cross_entropy",
        has_batch_stats=False,
    )

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    # embedding + lm_head don't do 6N of matmul work per token
    n_embed = vocab * d_model  # tok_emb; lm_head IS a matmul, keep it
    return model, state, batch, step_fn, n_params, n_embed


def chain_fn(step_fn, batch, n_steps):
    import jax

    def body(state, _):
        state, metrics = step_fn(state, batch)
        return state, metrics["loss"]

    # donate the carried state: without aliasing, argument + output trees
    # double the resident optimizer state (measured: 350m B=4 remat probe
    # reported 14.9 GiB peak un-donated)
    @functools.partial(jax.jit, donate_argnums=0)
    def chain(state):
        return jax.lax.scan(body, state, None, length=n_steps)

    return chain


def measure(args) -> dict:
    import jax

    peak = peak_bf16()  # before any work: no peak on record, no run
    t_build = time.perf_counter()
    model, state, batch, step_fn, n_params, n_embed = build(args)
    jax.block_until_ready(state.params)

    chain = chain_fn(step_fn, batch, args.steps)
    compiled = chain.lower(state).compile()
    compile_s = time.perf_counter() - t_build
    mem = compiled.memory_analysis()
    peak_gb = None
    if mem is not None:
        peak_gb = round(
            (
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0)
            )
            / 2**30,
            2,
        )
        print(f"# peak HBM (XLA estimate): {peak_gb} GiB", file=sys.stderr)
        if args.mem_only:
            return {
                "preset": args.preset, "seq": args.seq,
                "batch": args.batch, "attn": args.attn,
                "remat": bool(args.remat), "peak_hbm_gib": peak_gb,
                "compile_s": round(compile_s, 1),
            }

    # executed FLOPs from XLA's own cost model (single un-scanned step so
    # scan-length bookkeeping can't distort it)
    cost = (
        jax.jit(step_fn).lower(state, batch).compile().cost_analysis()
    )
    if isinstance(cost, (list, tuple)):  # CPU backend: one dict per device
        cost = cost[0] if cost else {}
    executed_flops = float(cost.get("flops", 0.0))

    # the one scan-aware analytic MFU numerator (models.utils has the
    # cost_analysis caveat)
    from pytorch_distributed_training_tutorials_tpu.models.utils import (
        model_flops_per_token,
    )

    d_model, n_layers, _, vocab = PRESETS[args.preset]
    tokens_per_step = args.batch * args.seq
    # lm_head participates in the 6N term; only tok_emb is excluded
    mflops_tok = model_flops_per_token(
        n_params - n_embed, d_model, n_layers, args.seq
    )
    model_flops = mflops_tok * tokens_per_step

    # prime the process's first D2H fetch outside every timed region
    state2, losses = compiled(state)
    float(losses[-1])

    # obs.MinOfN: stalls (> 5x median) stay visible in the receipt instead
    # of silently widening the min; priming above is the warmup
    holder = {"state": state2}

    def run_chain():
        holder["state"], losses = compiled(holder["state"])
        float(losses[-1])  # close the region with a real fetch

    from pytorch_distributed_training_tutorials_tpu.obs import MinOfN

    timing = MinOfN(n=args.reps, warmup=False).measure(run_chain)
    state2 = holder["state"]
    samples = timing.samples_s
    step_s = timing.best_s / args.steps

    fused = getattr(args, "fused", False)
    out = {
        "preset": args.preset,
        "d_model": d_model,
        "n_layers": n_layers,
        "vocab": vocab,
        "seq": args.seq,
        "batch": args.batch,
        "loss": "fused_cross_entropy" if fused else "cross_entropy",
        "optimizer": "fused_adamw" if fused else "adamw",
        "attn": args.attn
        + (f"({args.block_q},{args.block_k})" if args.attn == "flash" else ""),
        "remat": bool(args.remat),
        "remat_policy": args.remat_policy,
        "scan_layers": not args.no_scan,
        "n_params": n_params,
        "steps_chained": args.steps,
        "wall_s_samples": [round(s, 3) for s in samples],
        "stalled_samples": timing.n_stalled,
        "step_ms": round(step_s * 1e3, 2),
        "tokens_per_s": round(tokens_per_step / step_s),
        "model_tflops_per_step": round(model_flops / 1e12, 3),
        "executed_tflops_per_step": round(executed_flops / 1e12, 3),
        "mfu": round(model_flops / step_s / peak, 4),
        "hw_util_executed": round(executed_flops / step_s / peak, 4),
        "compile_s": round(compile_s, 1),
        "peak_hbm_gib": peak_gb,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }

    if args.trace:
        import shutil

        from pytorch_distributed_training_tutorials_tpu.obs import StepReport
        from pytorch_distributed_training_tutorials_tpu.utils import profiling

        logdir = "/tmp/jax-trace-lm"
        shutil.rmtree(logdir, ignore_errors=True)
        with profiling.trace(logdir):
            state2, losses = compiled(state2)
            float(losses[-1])
        # HLO-verified classification (obs.trace): leaf/wrapper split plus
        # the where-did-the-step-go categories, not just a total
        report = StepReport.from_trace(
            logdir, hlo=compiled.as_text(), steps=args.steps
        )
        dev_step_s = report.step_us / 1e6
        out["trace_step_ms"] = round(dev_step_s * 1e3, 2)
        out["trace_mfu"] = round(model_flops / dev_step_s / peak, 4)
        out["trace_hw_util"] = round(executed_flops / dev_step_s / peak, 4)
        out["trace_report"] = report.to_dict()
    return out




def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", choices=sorted(PRESETS), default="760m")
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--attn", choices=["dense", "flash"], default="flash")
    p.add_argument("--block_q", type=int, default=1024)
    p.add_argument("--block_k", type=int, default=1024)
    p.add_argument("--remat", action="store_true", default=True)
    p.add_argument("--no_remat", dest="remat", action="store_false")
    p.add_argument("--remat_policy", choices=["dots", "dots_attn"],
                   default="dots")
    p.add_argument("--no_scan", action="store_true", default=True,
                   help="unrolled layers (the training winner; see module "
                   "docstring)")
    p.add_argument("--scan", dest="no_scan", action="store_false")
    # 12 chained steps: launch + fetch are paid once per chain regardless
    # of its length, so a longer chain moves the wall number toward the
    # device rate (what a launch costs on the chip: not measured)
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--trace", action="store_true")
    p.add_argument("--mem_only", action="store_true")
    p.add_argument("--fused", action="store_true",
                   help="also run the fused-tail arm (logits-free "
                   "ops.fused_loss + ops.fused_optim AdamW) and emit both "
                   "arms in the JSON")
    p.add_argument("--json", default=None)
    return p.parse_args(argv)


def main() -> None:
    from pytorch_distributed_training_tutorials_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    args = parse()
    if args.fused:
        # side-by-side receipt: identical model/batch/chain, only the
        # loss+optimizer tail differs between the arms
        base = argparse.Namespace(**vars(args))
        base.fused = False
        r = {"baseline": measure(base), "fused": measure(args)}
    else:
        r = measure(args)
    from pytorch_distributed_training_tutorials_tpu.obs import make_receipt, write_receipt

    r = make_receipt("lm_headline", r)
    print(json.dumps(r))
    write_receipt(args.json, r)


if __name__ == "__main__":
    main()

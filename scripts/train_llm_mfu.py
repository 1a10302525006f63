"""LM train-step MFU on the real chip — the TRAIN_LLM_r05.json receipt.

The framework's deepest asset is the transformer stack. This script
measures what fraction of the chip's bf16 peak
(``bench.lm_headline.PEAK_BF16_BY_DEVICE_KIND``; a device that is not in
the table raises, so a CPU run reports nothing) a full `TransformerLM`
train step achieves — the
standard headline metric for a distributed-training framework — and
sweeps the knobs that move it (remat, attention kernel + block sizes,
batch, sequence length, the fused loss/optimizer tail).

The measurement engine (model build, lax.scan chain timing, analytic
model-FLOPs numerator, tracing) is ``bench.lm_headline.measure`` — one
copy of the methodology; this script owns only the sweep grid and its
CLI defaults. Methodology notes live in that module's docstring; the
scan-aware analytic-FLOPs caveat (XLA cost analysis counts a scan body
once, not times n_layers) in ``models.utils.model_flops_per_token``.

Run on the real chip:

    python scripts/train_llm_mfu.py --sweep --json sweep.json
    python scripts/train_llm_mfu.py --preset 350m --remat --trace
    python scripts/train_llm_mfu.py --preset 350m --remat --remat_policy \
        dots --no_scan --fused   # fused-tail arm vs baseline, side by side

(The committed TRAIN_LLM_r05.json receipt comes from the tuned-winner
CLI, ``python -m pytorch_distributed_training_tutorials_tpu.bench.lm_headline`` — 12-step chain;
this sweep harness defaults to 8-step chains, ~1.5 MFU points more
launch-amortization per row, fine for RELATIVE comparisons.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pytorch_distributed_training_tutorials_tpu.bench.lm_headline import (  # noqa: E402
    PRESETS,
    measure,
)


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", choices=sorted(PRESETS), default="350m")
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--attn", choices=["dense", "flash"], default="flash")
    p.add_argument("--block_q", type=int, default=512)
    p.add_argument("--block_k", type=int, default=512)
    p.add_argument("--remat", action="store_true")
    p.add_argument("--no_scan", action="store_true",
                   help="unroll the layer stack instead of nn.scan: "
                   "longer compiles, but no scan-carry activation "
                   "stacking (the dynamic-update-slice copies measured "
                   "~21%% of device time in the scanned 350m step)")
    p.add_argument("--remat_policy", choices=["dots", "dots_attn"], default=None,
                   help="what remat may keep: None = recompute all, "
                   "'dots' = save matmul outputs (checkpoint_dots_with_"
                   "no_batch_dims_saveable)")
    p.add_argument("--steps", type=int, default=8,
                   help="steps per compiled lax.scan chain")
    p.add_argument("--reps", type=int, default=3, help="min-of-N chain runs")
    p.add_argument("--trace", action="store_true",
                   help="capture a device trace of one chain run")
    p.add_argument("--mem_only", action="store_true",
                   help="compile and report XLA peak-memory estimate only")
    p.add_argument("--fused", action="store_true",
                   help="fused tail: logits-free blockwise cross entropy "
                   "(ops.fused_loss) + single-pass fused AdamW "
                   "(ops.fused_optim). Single-point runs emit baseline and "
                   "fused arms side by side; with --sweep every grid row "
                   "runs fused")
    p.add_argument("--sweep", action="store_true",
                   help="run the round-5 tuning table instead of one point")
    p.add_argument("--json", default=None, help="write results JSON here")
    return p.parse_args(argv)


# Memory-feasible grid (probed with --mem_only on the v5e's 15.75 GiB
# HBM: 350m B=8 remat 10.8 GiB, B=16 remat 14.1 GiB; B=8 WITHOUT remat
# needs 32.5 GiB — no-remat only fits at toy batch, so remat is not a
# tuning choice at this scale, it is the enabler of real batch sizes).
SWEEP = [
    # (preset, seq, batch, attn, block_q, block_k, remat[, remat_policy])
    # round B: remat_policy="dots" (save projection/FFN matmul outputs,
    # recompute attention internals + elementwise) and block_k variants
    ("350m", 2048, 8, "flash", 512, 1024, True, "dots"),
    ("350m", 2048, 4, "flash", 512, 1024, True, "dots"),
    ("350m", 2048, 8, "flash", 512, 2048, True, None),
    ("350m", 2048, 8, "flash", 256, 1024, True, None),
    ("125m", 2048, 32, "flash", 512, 1024, True, None),
    ("125m", 2048, 16, "flash", 512, 1024, True, "dots"),
    ("760m", 2048, 2, "flash", 512, 1024, True, None),
    ("760m", 2048, 2, "flash", 512, 1024, True, "dots"),
]


def main() -> None:
    from pytorch_distributed_training_tutorials_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    args = parse()

    results = []
    if args.sweep:
        for point in SWEEP:
            preset, seq, batch, attn, bq, bk, remat = point[:7]
            a = argparse.Namespace(**vars(args))
            a.preset, a.seq, a.batch, a.attn = preset, seq, batch, attn
            a.block_q, a.block_k, a.remat = bq, bk, remat
            a.remat_policy = point[7] if len(point) > 7 else None
            try:
                r = measure(a)
            except Exception as e:  # OOM points are data, not crashes
                r = {
                    "preset": preset, "seq": seq, "batch": batch,
                    "attn": attn, "remat": remat,
                    "error": f"{type(e).__name__}: {str(e)[:200]}",
                }
            results.append(r)
            print(json.dumps(r))
    elif args.fused:
        # side-by-side arms, identical model/batch/chain (the
        # bench.lm_headline --fused receipt shape)
        base = argparse.Namespace(**vars(args))
        base.fused = False
        r = {"baseline": measure(base), "fused": measure(args)}
        results.append(r)
        print(json.dumps(r, indent=2))
    else:
        r = measure(args)
        results.append(r)
        print(json.dumps(r, indent=2))

    if args.json:
        from pytorch_distributed_training_tutorials_tpu.obs import make_receipt, write_receipt

        # the schema'd envelope (obs.receipt): git sha / jax / backend ride
        # with the sweep rows, so a receipt can't outlive knowing what
        # produced it
        write_receipt(
            args.json, make_receipt("llm_mfu_sweep", {"results": results})
        )
        print(f"results -> {args.json}")


if __name__ == "__main__":
    main()

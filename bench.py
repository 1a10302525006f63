"""Headline benchmark: images/sec/chip, ResNet-18 / MNIST, data-parallel.

The BASELINE.json north-star (``BASELINE.json:2``): data-parallel ResNet-18 on
MNIST, reported per chip. The reference publishes no numbers
(``BASELINE.json:13``), so ``vs_baseline`` is reported against
``BASELINE_IMAGES_PER_SEC_PER_CHIP`` below — this repo's first recorded TPU
run, so later rounds measure improvement against round 1.

The headline number is the **end-to-end training loop** including the input
pipeline — not a cached batch replayed. The input pipeline is the
device-resident one (``data/resident.py``): the dataset is placed in HBM
once, and the measured region is a multi-epoch ``lax.scan`` whose body
gathers each step's batch on device — one XLA launch and one host fetch for
the whole region, so launch and fetch are paid once per run and not once per
epoch (what they cost on the chip is not measured). The JSON line carries
the honesty
metadata: whether the data was a synthetic surrogate (no network egress in
the build env), a breakdown (streaming train, raw H2D ceiling, train step
alone), and the held-out eval accuracy against the stated 0.99 target (the
BASELINE "reaches reference accuracy" demonstration, measured unbiased —
wrap-padding masked).

Prints exactly one JSON line on stdout
(``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}``);
progress/epoch lines go to stderr.
"""

from __future__ import annotations

import contextlib
import json
import sys

# Round-1 first measurement on one TPU v5e chip (bf16 compute).
# Round-1 measured the train step on a cached batch; from round 2 the headline
# includes the input pipeline. Later rounds divide by this to show the trend.
BASELINE_IMAGES_PER_SEC_PER_CHIP = 46400.0


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--real", action="store_true",
        help="require REAL MNIST on disk (scripts/fetch_datasets.py): "
        "refuse to bench the synthetic surrogate, so the receipt can "
        "only be a real-data receipt",
    )
    ap.add_argument(
        "--quiet", action="store_true",
        help="silence per-epoch trainer chatter on stderr (structured "
        "metrics still record; the JSON line is unaffected)",
    )
    args = ap.parse_args()

    from pytorch_distributed_training_tutorials_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    import jax

    import optax

    from pytorch_distributed_training_tutorials_tpu.bench.headline import (
        make_headline_setup,
        make_step_chain,
    )
    from pytorch_distributed_training_tutorials_tpu.data import (
        ChunkedStreamingLoader,
        DeviceResidentLoader,
        mnist,
    )
    from pytorch_distributed_training_tutorials_tpu.obs import DriftBracket, MinOfN, make_receipt
    from pytorch_distributed_training_tutorials_tpu.train import Trainer

    # the canonical workload (uint8-resident MNIST, bf16 cifar-stem
    # ResNet-18, SGD+momentum)
    setup = make_headline_setup(per_device_batch=512, quiet=args.quiet)
    mesh, ds, loader, trainer = (
        setup.mesh, setup.dataset, setup.loader, setup.trainer
    )
    if args.real and ds.synthetic:
        raise SystemExit(
            "--real: no MNIST idx files under DATA_DIR — run "
            "scripts/fetch_datasets.py (needs network) first; refusing "
            "to report a synthetic receipt as real"
        )
    model = trainer.model
    n_chips = mesh.devices.size
    per_device_batch = setup.per_device_batch

    # 6 epochs per fused launch: the fused region pays ONE launch + fetch
    # regardless of length, so a longer span shrinks the per-epoch share
    # of it (that cost on the chip: not measured). Accuracy trains a few
    # epochs longer; the target check is unaffected (MNIST plateaus
    # >=0.996 well before epoch 10).
    fused_epochs = 6
    with contextlib.redirect_stdout(sys.stderr):
        # TIMING DISCIPLINE: dispatch is asynchronous, so every timed
        # region below is (a) entered with the process's first fetch
        # already PRIMED outside it and (b) closed by a real fetch.

        # Breakdown leg 1a: streaming END-TO-END TRAINING — the path a
        # larger-than-HBM dataset actually takes: chunked H2D (16 steps per
        # transfer), background prefetch, each chunk trained as one scanned
        # launch (data/streaming.py). Its ceiling is the host-to-device
        # bandwidth, which leg 1b measures in the same window (on the
        # chip's own host: not measured).
        chunked = ChunkedStreamingLoader(
            ds, per_device_batch, mesh, seed=0,
            steps_per_chunk=16, transform=loader.transform,
        )
        stream_trainer = Trainer(
            model, chunked, optax.sgd(0.05, momentum=0.9),
            loss="cross_entropy", quiet=args.quiet,
        )
        # Breakdown leg 1: streaming train vs the RAW H2D ceiling. The
        # ceiling is pure device_put of the same dataset bytes in
        # chunk-sized buffers, primed and closed by a ONE-element terminal
        # fetch. A shared host's bandwidth can drift, so the ceiling is
        # measured immediately BEFORE and AFTER the streaming epoch and
        # averaged — bracketing the drift instead of racing it.
        import numpy as np

        n_bufs = 7
        rows_needed = chunked.steps_per_chunk * chunked.global_batch
        # np.resize wraps when the dataset has fewer rows than one chunk
        # needs (16 * 512 * n_chips can exceed 60000 on multi-chip hosts)
        chunk_imgs = np.resize(
            ds.arrays[0], (rows_needed, *ds.arrays[0].shape[1:])
        ).reshape(
            chunked.steps_per_chunk, chunked.global_batch,
            *ds.arrays[0].shape[1:]
        )

        def fetch_scalar(buf):
            # device-side index, then a ONE-element D2H — fetching the
            # whole buffer would charge MBs of D2H to the H2D timing
            return float(buf[-1, -1].ravel()[-1])

        def h2d_ceiling():
            bufs = [jax.device_put(chunk_imgs) for _ in range(n_bufs)]
            jax.block_until_ready(bufs)
            fetch_scalar(bufs[-1])

        # warm + prime the put path (first-fetch stall lives elsewhere but
        # the first put of a new shape pays layout/allocator setup)
        bufs = [jax.device_put(chunk_imgs) for _ in range(2)]
        jax.block_until_ready(bufs)
        fetch_scalar(bufs[-1])
        del bufs

        # compiles both chunk lengths AND primes the first-fetch stall
        # (the per-epoch loss fetch) outside the timed region — and
        # outside the bracket: epoch 0's compile takes long enough for
        # the window to drift
        stream_trainer._run_epoch(0)
        # obs.DriftBracket: the ceiling leg runs immediately BEFORE and
        # AFTER the streaming epoch; ~1.0 drift = stable window (the
        # streaming fraction below is trustworthy), >>1 = the fraction is
        # drift noise around the controlled same-process finding (~1.0)
        bracket = DriftBracket(
            h2d_ceiling, payload_bytes=n_bufs * chunk_imgs.nbytes
        ).around(
            lambda: stream_trainer._run_epoch(1)["samples_per_sec"]
        )
        stream_train_images_s = bracket.result
        dt = (bracket.before_s + bracket.after_s) / 2
        h2d_drift = bracket.drift
        h2d_mb_s = n_bufs * chunk_imgs.nbytes / 1e6 / dt
        h2d_images_s = (
            n_bufs * chunked.steps_per_chunk * chunked.global_batch / dt
        )

        # Headline: epoch 0 compiles the per-epoch program; the first fused
        # call compiles the fused-run program (different scan length); the
        # best of the next two fused calls is the honest end-to-end
        # measurement: dataset residency, on-device gather, train step, ONE
        # launch + ONE host fetch for the whole region. Max-of-2 on
        # throughput = min-of-2 on time: the headline must not be hostage
        # to one stalled launch on a shared host.
        trainer._run_epoch(0)
        trainer.run_epochs_fused(1, fused_epochs)  # compile warmup
        e2e = max(
            trainer.run_epochs_fused(
                1 + k * fused_epochs, fused_epochs
            )["samples_per_sec"]
            for k in range(1, 3)
        )

        # Breakdown leg 2: train step alone on a cached batch — a jitted
        # scan of N chained steps, timed as one launch + one fetch (timing
        # individual dispatches measures the enqueue, not the device).
        # the cached batch is normalized by the loader's jitted transform
        # (same bf16 dtype semantics as the in-scan path); unroll=8
        # amortizes while-loop bookkeeping and halves the loop-boundary
        # state copies. The fused-epoch leg unrolls x8 too
        # (make_headline_setup).
        chain_len = 256
        chain = make_step_chain(setup, chain_len, unroll=8)

        # obs.MinOfN(n=2): the minimum of two closed timed regions rejects
        # a one-off stall of the shared host, and the warmup run is the
        # compile + first-fetch priming
        holder = {"state": trainer.state}

        def chain_run():
            holder["state"], losses = chain(holder["state"])
            float(losses[-1])

        step_timing = MinOfN(n=2).measure(chain_run)
        step_images_s = chain_len * loader.global_batch / step_timing.best_s

        # Accuracy demonstration (BASELINE north star: "reaches reference
        # accuracy"): evaluate on the held-out test split with wrap-padding
        # masked (unbiased). Target: 0.99 — conventional MNIST ResNet
        # accuracy. The surrogate is tuned so the target is FALSIFIABLE
        # (data/datasets.py signal=0.35: healthy training measures 0.9961
        # with nonzero loss; the signal=0.30 negative control misses at
        # 0.9867 after 7 epochs AND still at 0.9863 after the full
        # 19-epoch span this bench now trains — re-measured round 5 when
        # fused_epochs doubled, so longer training cannot sneak a degraded
        # config past the target; a broken config fails outright —
        # tests/test_accuracy_falsifiable.py pins that control).
        # `synthetic` says which data this was.
        test_loader = DeviceResidentLoader(
            mnist("test", raw=True),
            per_device_batch,
            mesh,
            seed=0,
            transform=loader.transform,
        )
        eval_metrics = trainer.evaluate(test_loader)

    per_chip = e2e / n_chips
    # the schema'd envelope (obs.receipt): payload keys stay top-level so
    # the one-JSON-line contract and its consumers are unchanged; the
    # envelope adds schema/kind/env (git sha, jax, mesh) + the drift window
    receipt = make_receipt(
        "bench_headline",
        {
                "metric": (
                    "images/sec/chip (ResNet-18 MNIST, data-parallel train, "
                    "end-to-end incl. input pipeline)"
                ),
                "value": round(per_chip, 1),
                "unit": "images/sec/chip",
                "vs_baseline": round(
                    per_chip / BASELINE_IMAGES_PER_SEC_PER_CHIP, 3
                ),
                "synthetic": bool(ds.synthetic),
                "n_chips": n_chips,
                "per_device_batch": per_device_batch,
                "eval_accuracy": round(eval_metrics["accuracy"], 4),
                "eval_loss": round(eval_metrics["loss"], 6),
                "accuracy_target": 0.99,
                "reaches_accuracy_target": bool(
                    eval_metrics["accuracy"] >= 0.99
                ),
                "breakdown": {
                    "streaming_train_images_per_sec_per_chip": round(
                        stream_train_images_s / n_chips, 1
                    ),
                    # the pipeline-alone leg is the RAW H2D ceiling (pure
                    # device_put, same bytes, same window) — streaming is
                    # judged as a fraction of it
                    "h2d_ceiling_images_per_sec_per_chip": round(
                        h2d_images_s / n_chips, 1
                    ),
                    "h2d_ceiling_mb_per_sec": round(h2d_mb_s, 2),
                    "h2d_window_drift": round(h2d_drift, 2),
                    "streaming_fraction_of_h2d_ceiling": round(
                        stream_train_images_s / max(h2d_images_s, 1e-9), 3
                    ),
                    "train_step_only_images_per_sec_per_chip": round(
                        step_images_s / n_chips, 1
                    ),
                    "train_step_only_stalled_samples": (
                        step_timing.n_stalled
                    ),
                },
        },
        mesh=mesh,
        drift=bracket.to_dict(),
    )
    print(json.dumps(receipt))


if __name__ == "__main__":
    main()

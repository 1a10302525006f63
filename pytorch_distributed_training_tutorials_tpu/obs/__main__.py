"""``python -m pytorch_distributed_training_tutorials_tpu.obs --selftest``: end-to-end smoke of the
observability layer on a tiny workload.

Exercises every pillar against whatever backend is available (the
tier-1 test runs it on the forced 8-device CPU mesh): trains a few steps
with a JSONL-sinked :class:`MetricsLogger`, times a jitted step chain
with :class:`MinOfN`, drives the flight-recorder pillar (histogram
sharding/merge vs numpy percentiles, a full lifecycle span, a
``graft-flightlog/v1`` dump round-tripped through disk and re-validated),
and emits an ``obs_selftest`` receipt through the schema'd writer. Prints
exactly one JSON line on stdout and exits non-zero on any validation
failure — a living receipt that the pipeline works.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile


def selftest(json_path: str | None = None) -> dict:
    import jax
    import optax

    from pytorch_distributed_training_tutorials_tpu.data import ShardedLoader, synthetic_regression
    from pytorch_distributed_training_tutorials_tpu.models import LinearRegressor
    from pytorch_distributed_training_tutorials_tpu.obs import (
        MetricsLogger,
        MinOfN,
        make_receipt,
        validate_receipt,
    )
    from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
    from pytorch_distributed_training_tutorials_tpu.train import Trainer

    problems: list[str] = []
    workdir = tempfile.mkdtemp(prefix="obs-selftest-")
    jsonl_path = os.path.join(workdir, "metrics.jsonl")

    # pillar 1: metrics through a quiet, JSONL-sinked logger
    mesh = create_mesh({"data": jax.device_count()})
    loader = ShardedLoader(
        synthetic_regression(size=256, in_dim=8, out_dim=1), 8, mesh
    )
    metrics = MetricsLogger(jsonl_path=jsonl_path, quiet=True)
    trainer = Trainer(
        LinearRegressor(in_dim=8), loader, optax.sgd(1e-2), loss="mse",
        metrics=metrics, log_every=2,
    )
    trainer.train(2)
    metrics.close()
    if not metrics.epoch_events():
        problems.append("no epoch events recorded")
    if not metrics.step_events():
        problems.append("no step events recorded")
    with open(jsonl_path) as f:
        jsonl_lines = [json.loads(line) for line in f if line.strip()]
    if len(jsonl_lines) != len(metrics.events):
        problems.append(
            f"jsonl sink ({len(jsonl_lines)}) != ring buffer "
            f"({len(metrics.events)})"
        )

    # pillar 2: MinOfN on a fetch-closed chain (warmup primes first fetch)
    steps = 4
    batch = next(iter(loader))

    def chain(s, b):
        return jax.lax.scan(
            lambda st, _: (trainer.train_step(st, b)[0], None),
            s, None, length=steps,
        )[0]

    compiled = jax.jit(chain).lower(trainer.state, batch).compile()
    timing = MinOfN(n=3).measure(
        lambda: jax.block_until_ready(compiled(trainer.state, batch))
    )
    if timing.best_s <= 0:
        problems.append("MinOfN produced a non-positive sample")

    # pillar 3: flight recorder + streaming histograms (ISSUE 10) —
    # jax-free, so this leg runs identically on any backend
    import math
    import random

    from pytorch_distributed_training_tutorials_tpu.obs import (
        FlightRecorder,
        LogHistogram,
        load_flightlog,
        validate_flightlog,
    )

    # histograms: shard a heavy-tailed sample over two recorders, merge,
    # and require every quantile within the documented one-bucket bound
    # of the exact sorted-sample value
    rng = random.Random(7)
    samples = [rng.lognormvariate(-3.0, 1.5) for _ in range(4000)]
    whole = LogHistogram()
    sharded = [LogHistogram(), LogHistogram()]
    for i, v in enumerate(samples):
        whole.record(v)
        sharded[i % 2].record(v)
    merged = sharded[0].merge(sharded[1])
    if merged.counts != whole.counts or merged.n != whole.n:
        problems.append("sharded histogram merge != whole-sample record")
    svals = sorted(samples)
    for q in (0.5, 0.95, 0.99):
        exact = svals[max(1, math.ceil(q * len(svals))) - 1]
        if abs(whole.quantile(q) - exact) > whole.rel_error_bound * exact:
            problems.append(
                f"histogram q={q} off by more than one bucket: "
                f"{whole.quantile(q)} vs exact {exact}"
            )
    # flight dump round-trip: one synthetic lifecycle + a fault, dumped
    # to disk, loaded back, re-validated
    flight_path = os.path.join(workdir, "flight.jsonl")
    rec = FlightRecorder(capacity=32, dump_path=flight_path)
    rec.request_submitted(0, p_len=4, max_new=8)
    rec.request_popped(0)
    rec.request_prefilled(0, slot=1)
    rec.chain_start(1, 2)
    rec.chain_end(tokens=8, occupancy=1)
    rec.fault("nonfinite", rid=0, slot=1, chain_step=3)
    rec.request_completed(0, "nonfinite", tokens=3)
    try:
        snaps = load_flightlog(flight_path)
        for snap in snaps:
            validate_flightlog(snap)
        if len(snaps) != 1:
            problems.append(f"{len(snaps)} flight dumps, expected 1")
        elif snaps[0]["trigger"].get("slot") != 1:
            problems.append("flight dump trigger lost the faulty slot")
        hist_rt = LogHistogram.from_dict(
            json.loads(json.dumps(whole.to_dict()))
        )
        if hist_rt.counts != whole.counts or (
            hist_rt.quantile(0.95) != whole.quantile(0.95)
        ):
            problems.append("histogram JSON round-trip changed state")
    except ValueError as e:
        problems.append(f"flight dump failed validation: {e}")
    fsum = rec.summary()
    if fsum["flight_spans_done"] != 1 or fsum["e2e_count"] != 1:
        problems.append(f"flight summary inconsistent: {fsum}")

    # pillar 4: the schema'd receipt, validated before it is reported
    receipt = make_receipt(
        "obs_selftest",
        {
            "last_epoch": metrics.last_epoch,
            "n_events": len(metrics.events),
            "timing": timing.to_dict(),
            "flight": fsum,
            "hist_rel_error_bound": whole.rel_error_bound,
            "problems": problems,
            "ok": not problems,
        },
        mesh=mesh,
    )
    problems.extend(validate_receipt(receipt, kind="obs_selftest"))
    receipt["ok"] = not problems
    receipt["problems"] = problems
    if json_path:
        with open(json_path, "w") as f:
            json.dump(receipt, f, indent=2)
            f.write("\n")
    shutil.rmtree(workdir, ignore_errors=True)
    return receipt


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="python -m pytorch_distributed_training_tutorials_tpu.obs")
    parser.add_argument(
        "--selftest", action="store_true",
        help="run the end-to-end observability smoke test",
    )
    parser.add_argument(
        "--json", default=None, help="also write the receipt to this path"
    )
    args = parser.parse_args(argv)
    if not args.selftest:
        parser.print_help()
        return 2
    from pytorch_distributed_training_tutorials_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    receipt = selftest(args.json)
    print(json.dumps(receipt))
    return 0 if receipt["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""Runbook for the deferred real-chip receipt debt — one chip session.

Every feature since round 5 shipped with its real-chip receipt recipe
documented but NOT taken (no chip in those sessions): the
fused train-step tail, the --server base arm, prefix splicing,
speculation, multi-tenant adapters, deadlines, the flight recorder,
request-loop pipelining, the fleet router, the paged KV pool,
tensor-parallel serving, the fused paged-attention kernel with int4
KV, prefill/decode disaggregation, and now SLO-tier preemption. This
script is the catch-up: it sequences all fourteen arms so the next
session with a chip runs ONE command instead of re-deriving fourteen
recipes from CLAUDE.md prose.

Sequencing is the point — every serving arm shares one --ckpt_dir, so
the ~10-min cold 1.2B quantize-on-load cost is paid exactly once (by
the base arm) and the other eight reuse the cached checkpoint; the
paged arm reuses it too (weights are window-agnostic, the KV pool is
config-sized). Outputs are named SERVING_rNN_<arm>.json /
TRAIN_LLM_rNN_fused.json so bench.regress fingerprints the arms apart
and each lands in the receipt history under its own config.

Deliberately stdlib-only and jax-free at import: building the command
list must work on any host (the CPU smoke test does exactly that);
only actually RUNNING the arms needs the chip.

Every --server arm runs with the runtime contract sentry ON by
default (ISSUE 19, no flag needed): the receipts below all carry
sentry_steady_recompiles / sentry_fetch_budget_ok /
sentry_reupload_bytes, so a contract break on the real chip — a
steady-state recompile, a stray host sync, a host-numpy re-upload —
names itself in the receipt (and auto-dumps a flight snapshot)
instead of silently costing the round. Pass --no-sentry only to
bisect sentry overhead itself; regress.py fingerprints the `sentry`
field so the two kinds of round never gate each other.

Usage:
    python scripts/receipt_session.py --round 6 --dry-run   # print plan
    python scripts/receipt_session.py --round 6             # run all
    python scripts/receipt_session.py --round 6 --only paged,fleet
    python scripts/receipt_session.py --round 6 --keep-going
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

# (arm name, expected receipt field family) — the per-arm flag deltas
# live in build_session; each arm is the exact recipe its CLAUDE.md
# section defers ("the real-chip receipt has NOT been taken yet").
ARM_NAMES = (
    "fused_mfu",   # bench.lm_headline --fused: the logits-free tail
    "base",        # --server: continuous-batching p50/p95 + tok/s
    "prefix",      # --prefix-overlap 0.7: radix splice TTFT win
    "spec",        # --spec-k 4: n-gram speculation on templated streams
    "adapters",    # --adapters 8: tenants-per-chip at fixed HBM
    "deadline",    # --deadline-s 2: latency cap at unchanged tok/s
    "flight",      # --flight-log: histogram tails + chain utilization
    "pipeline",    # --pipeline-depth 2: wall tok/s vs device rate
    "fleet",       # --replicas 2 --qps 8: aggregate tok/s + ledger_ok
    "paged",       # --paged @ 4096 window: hbm_high_water_bytes claim
    "paged_int4",  # --kv-bits 4 --paged-kernel: 2x pages, fused reads
    "tp",          # --tp 4: head-sharded decode, per-chip KV at 1/tp
    "disagg",      # --disaggregate 1p2d: role-split fleet, handoff TTFT
    "slo",         # --slo --qps 8: priority classes, preempt/resume wait
)


def build_session(round_no: int, ckpt_dir: str, out_dir: str):
    """Return the full ordered [(arm_name, argv), ...] plan.

    Pure function of its inputs so the CPU smoke test can pin the plan
    without a chip: argv lists are ready for subprocess.run.
    """
    rr = f"r{round_no:02d}"
    py = sys.executable

    def out(name: str) -> str:
        return os.path.join(out_dir, name)

    serve = [
        py, "examples/serve_llm_int8.py", "--preset", "1b",
        "--ckpt_dir", ckpt_dir,
    ]

    def srv(name: str, *extra: str) -> tuple[str, list[str]]:
        return name, [
            *serve, "--server", *extra,
            "--json", out(f"SERVING_{rr}_{name}.json"),
        ]

    return [
        (
            "fused_mfu",
            [
                py, "-m",
                "pytorch_distributed_training_tutorials_tpu.bench.lm_headline",
                "--fused", "--json", out(f"TRAIN_LLM_{rr}_fused.json"),
            ],
        ),
        # base FIRST among the serving arms: it pays the cold
        # quantize-on-load, everything after hits the ckpt_dir cache
        srv("base"),
        srv("prefix", "--prefix-overlap", "0.7"),
        srv("spec", "--spec-k", "4"),
        srv("adapters", "--adapters", "8", "--lora-rank", "8"),
        srv("deadline", "--deadline-s", "2"),
        srv("flight", "--flight-log", out(f"FLIGHT_{rr}.jsonl")),
        srv("pipeline", "--pipeline-depth", "2", "--prefill-chunk", "512"),
        srv("fleet", "--replicas", "2", "--qps", "8"),
        # long-window paged arm: slot count decoupled from the 4096
        # window; the interesting receipt field is hbm_high_water_bytes
        srv("paged", "--max_seq_len", "4096", "--paged"),
        # int4 + fused-kernel arm (ISSUE 17): packed-nibble KV fits 2x
        # the pages of the int8 arm at equal hbm_high_water_bytes, and
        # the Pallas page-walk kernel drops the dense gathered-window
        # traffic — expect MORE concurrent slots at the 4096 window and
        # a shrunk gather+attention class in the obs.StepReport trace
        # breakdown
        srv("paged_int4", "--max_seq_len", "4096", "--paged",
            "--kv-bits", "4", "--paged-kernel"),
        # tensor-parallel arm: head-sharded decode over the model axis;
        # the interesting fields are tp_kv_bytes_per_chip (1/tp of the
        # global cache) and tp_hlo_ok at tok/s within a few % of base
        srv("tp", "--tp", "4"),
        # disaggregated arm (ISSUE 18): one prefill-specialized replica
        # feeds two decode-specialized replicas through device-side KV
        # handoffs; the interesting fields are ttft_p95 under mixed
        # long/short traffic (prefill no longer steals decode rounds),
        # handoffs_moved == requests, and ledger_ok=true — decode tok/s
        # itself should match the fleet arm
        srv("disagg", "--disaggregate", "1p2d", "--qps", "8"),
        # SLO-tier arm (ISSUE 20): two priority classes over one engine;
        # high-class arrivals preempt the lowest-class active slot at
        # the chain boundary (KV swap to host, resume token-exact). The
        # interesting fields are per-class ttft_p95 split (class 0 flat
        # under class-1 load), n_preemptions > 0 only at saturation,
        # and preempt_wait_p95 — aggregate tok/s should match base
        srv("slo", "--slo", "--qps", "8"),
    ]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="run the deferred real-chip receipt arms in sequence"
    )
    ap.add_argument("--round", type=int, required=True, dest="round_no",
                    help="receipt round number (names the output files)")
    ap.add_argument(
        "--ckpt-dir", dest="ckpt_dir",
        default=os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "llm_int8_1b"
        ),
        help="shared serving checkpoint cache (cold load paid once)",
    )
    ap.add_argument("--out-dir", dest="out_dir", default=".",
                    help="where receipt JSON / flight logs land")
    ap.add_argument(
        "--only", default=None,
        help="comma-separated arm subset (names: %s)" % ",".join(ARM_NAMES),
    )
    ap.add_argument("--dry-run", action="store_true",
                    help="print the command plan without running anything")
    ap.add_argument(
        "--keep-going", action="store_true",
        help="continue past a failed arm (default: stop — a lost chip "
        "fails every later arm the same way)",
    )
    args = ap.parse_args(argv)

    plan = build_session(args.round_no, args.ckpt_dir, args.out_dir)
    if args.only:
        want = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = sorted(set(want) - set(ARM_NAMES))
        if unknown:
            ap.error(f"unknown arm(s): {', '.join(unknown)}")
        plan = [(n, cmd) for n, cmd in plan if n in want]

    for name, cmd in plan:
        print(f"[{name}] {' '.join(cmd)}")
    if args.dry_run:
        return 0

    os.makedirs(args.out_dir, exist_ok=True)
    failures: list[str] = []
    for name, cmd in plan:
        print(f"\n=== arm {name} ===", flush=True)
        t0 = time.monotonic()
        proc = subprocess.run(cmd)
        dt = time.monotonic() - t0
        if proc.returncode == 0:
            print(f"=== arm {name}: ok in {dt:.0f}s ===", flush=True)
        else:
            print(f"=== arm {name}: FAILED (rc={proc.returncode}, "
                  f"{dt:.0f}s) ===", flush=True)
            failures.append(name)
            if not args.keep_going:
                break
    if failures:
        print(f"\nfailed arms: {', '.join(failures)}")
        return 1
    print(f"\nall {len(plan)} arm(s) complete; receipts in {args.out_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

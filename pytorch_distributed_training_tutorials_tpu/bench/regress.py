"""Receipt-trajectory regression gate: compare rounds, fail on decay.

Every benchmark entry point stamps a ``graft-receipt/v1`` envelope
(:mod:`..obs.receipt`) and the repo checks the JSON in per round
(``BENCH_r0*.json``, ``SERVING_r0*.json``, ``TRAIN_LLM_r05.json``, ...),
but until now nothing COMPARED rounds — a perf regression only surfaced
if someone eyeballed two files. This is the minimal standing gate
(ROADMAP item 4): load every receipt, key it by (kind, measurement
config), order each key's receipts by round (the ``_rNN`` filename
convention), and exit nonzero when the newest round's throughput/MFU
falls more than ``--tolerance`` below the best earlier round.

Scope decisions that keep the cut honest:

- HIGHER-IS-BETTER rate metrics are gated (tok/s families + MFU + the
  bench headline ``value`` when its ``unit`` is a rate), and — since the
  flight recorder made the tails stable (ISSUE 10) — so are the
  LOWER-IS-BETTER p95 latency metrics (``LATENCY_METRICS``): the latest
  round must stay within ``(1 + tolerance) *`` the lowest earlier p95.
  p50s and wall-clock fields stay informational (a shared host's
  launch stalls set their noise floor);
- receipts only compare within an identical measurement config
  (preset/batch/lengths/dtype/... fingerprint): the 1b f32 and 1b-gqa
  int8 serving receipts are different experiments, not a trajectory;
- legacy (pre-schema) receipts participate — kind is inferred from the
  filename prefix and the payload validated by
  :func:`..obs.receipt.validate_receipt`'s legacy mode — so the gate
  covers the repo's whole measurement history, not just new rounds.

Run: ``python -m pytorch_distributed_training_tutorials_tpu.bench.regress [paths...]
[--tolerance 0.05] [--json]``. No paths = every ``*.json`` at the repo
root. jax-free by construction (receipt validation never imports jax),
so tier-1 smokes it as pure host code (tests/test_regress.py).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

from pytorch_distributed_training_tutorials_tpu.obs.receipt import (
    load_receipt,
    validate_receipt,
)

# gated metrics: higher is better; "value" only when the unit is a rate
RATE_METRICS = (
    "tokens_per_s",
    "decode_tok_per_s",
    "server_tok_per_s",
    "tok_per_s",
    "mfu",
)

# gated metrics: LOWER is better (ISSUE 10). p95 tails come from the
# flight recorder's streaming histograms, so they are finally stable
# enough to gate: the bucket geometry (not sort order over a noisy
# sample) sets their resolution, and the recorder primes/fetch contract
# keeps warmup compiles out of the sample. p50s stay informational.
LATENCY_METRICS = (
    "server_p95_latency_s",
    "server_ttft_p95_s",
    "ttft_p95_s",
    "e2e_p95_s",
)

# payload fields that identify WHAT was measured — receipts compare only
# within an identical fingerprint
CONFIG_FIELDS = (
    "metric", "unit", "workload", "preset", "batch", "per_device_batch",
    "seq", "prompt_len", "new_tokens", "max_seq_len", "kv_cache_dtype",
    "tp", "scan_layers", "attn", "n_chips", "n_devices", "temperature",
    "flash_prefill", "prefix_overlap",
    # speculative decoding: k and the draft n-gram order change what a
    # tok/s number MEANS (a spec round must never gate — or be gated
    # by — a non-speculative one); acceptance RATE stays out of the
    # fingerprint on purpose, it is a workload-dependent outcome, not
    # part of the configuration
    "spec_k", "spec_ngram", "speculative",
    # multi-tenant LoRA serving: bank geometry changes the measurement
    # (per-slot factor gathers in every forward), so adapter rounds and
    # base rounds are different experiments; occupancy/traffic counters
    # (adapters_registered, adapter_requests) stay out — workload
    # outcomes, not configuration
    "n_adapters", "lora_rank", "adapters",
    # robustness layer (ISSUE 9): fault injection / deadlines / the
    # finite-logits guard change what a round measures, so chaos rounds
    # never gate — or get gated by — clean rounds. The fault COUNTERS
    # (deadline_expired, cancelled, nonfinite_quarantined, steps_skipped)
    # stay out deliberately: they are outcomes of the traffic, not
    # configuration of the experiment
    "chaos", "deadline_s", "guard_nonfinite",
    # flight recorder (ISSUE 10): instrumented rounds carry host-side
    # bookkeeping in the request loop, so they never gate — or get gated
    # by — bare rounds; the recorder's own counters (flight_events,
    # flight_dumps, ...) stay out, outcomes not configuration
    "flight",
    # request-loop pipelining (ISSUE 11): double-buffered chains and
    # chunked prefill change the dispatch schedule a tok/s or TTFT
    # number was measured under, so pipelined and serial rounds are
    # different experiments; n_chunks stays out — an outcome of the
    # traffic mix, not configuration
    "pipeline_depth", "prefill_chunk",
    # fleet router (ISSUE 12): replica count, hedging delay, affinity
    # depth, and the offered load change what an aggregate tok/s or
    # tail-latency number MEANS, so fleet rounds and single-engine
    # rounds are different experiments; the health/ledger counters
    # (replicas_dead, redispatched, hedged, probes, ...) stay out
    # deliberately — outcomes of the injected faults and traffic, not
    # configuration of the experiment
    "n_replicas", "hedge", "affinity", "qps",
    # paged KV cache (ISSUE 13): the pool geometry changes what a tok/s
    # or HBM number MEANS (gathered page reads vs whole-slot reads,
    # admission by pages vs slots), so paged and whole-slot rounds are
    # different experiments; the occupancy counters (pages_high_water,
    # pages_shares, pages_sheds, hbm_high_water_bytes) stay out
    # deliberately — outcomes of the traffic, not configuration
    "paged", "page_size", "pool_pages",
    # fused paged attention + quantized KV (ISSUE 17): the page-walk
    # kernel vs the jnp.take gather read path and the KV storage width
    # (0 = full precision, 8 = int8 + f32 scales, 4 = packed nibbles +
    # bf16 scales) each change what a tok/s or HBM number MEANS, so
    # int4/kernel rounds never gate — or get gated by — int8/gather
    # ones; page_bytes stays out (derived from geometry + kv_bits, not
    # an independent knob)
    "kv_bits", "paged_kernel",
    # sharded serving (ISSUE 15): "tp" above already fingerprints the
    # TP width (the int8 decode receipts have carried it since r04);
    # mesh_shape additionally separates mesh GEOMETRIES at equal tp
    # (model:4 vs data:2,model:2 compile different collective schedules,
    # so their tok/s are different experiments). The audit outcomes
    # (tp_collectives, tp_hlo_ok) and the per-chip KV footprint stay
    # out — outcomes, not configuration
    "mesh_shape",
    # prefill/decode disaggregation (ISSUE 18): an engine's role and the
    # fleet's role geometry change what a tok/s or TTFT number MEANS
    # (a prefill replica's "throughput" is segments, a decode replica
    # never prefills, and 1p2d vs 2p1d are different experiments), so
    # disaggregated and monolithic rounds never gate each other; the
    # handoff counters (handoffs_out/in/moved) stay out — outcomes of
    # the traffic, not configuration
    "role", "n_prefill_replicas", "n_decode_replicas",
    # contract sentry (ISSUE 19): an instrumented round carries a
    # jax.device_get wrapper + a compile listener in the request loop
    # (host-only, but still instrumentation), so sentry-on and bare
    # rounds never gate each other; the sentry's own counters
    # (sentry_compiles, sentry_steady_recompiles, sentry_fetched,
    # sentry_reupload_bytes, ...) stay out — outcomes, not configuration
    "sentry",
    # SLO tiers (ISSUE 20): the class count and the preemption flag
    # change what a tok/s or per-class TTFT number MEANS (a preempting
    # engine trades low-class latency for high-class tails), so SLO
    # rounds never gate — or get gated by — FIFO rounds; the swap
    # counters (n_preemptions, n_swaps_out/in, swapped_now) and the
    # preempted-wait histogram stay out — outcomes of the traffic mix,
    # not configuration
    "priority_classes", "preemption",
)

_ROUND_RE = re.compile(r"_r(\d+)")


def _payload(obj: dict) -> dict:
    """The measurement dict: bench.py's min-of-N wrapper nests it under
    ``parsed`` (the checked-in BENCH_r0*.json shape); everything else is
    already flat."""
    parsed = obj.get("parsed")
    if isinstance(parsed, dict):
        return {**obj, **parsed}
    return obj


def _kind(obj: dict, path: str) -> str:
    """Schema'd receipts carry ``kind``; legacy ones are keyed by the
    filename family (``SERVING_r04_long.json`` -> ``serving``)."""
    if isinstance(obj.get("kind"), str):
        return obj["kind"]
    stem = os.path.basename(path)
    return stem.split("_")[0].split(".")[0].lower()


def _round(path: str) -> int:
    """Round number from the ``_rNN`` filename convention; -1 when the
    file carries none (sorts before every numbered round)."""
    m = _ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else -1


def _metrics(payload: dict) -> dict[str, float]:
    out = {}
    for name in RATE_METRICS + LATENCY_METRICS:
        v = payload.get(name)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[name] = float(v)
    v, unit = payload.get("value"), payload.get("unit")
    if (isinstance(v, (int, float)) and not isinstance(v, bool)
            and isinstance(unit, str) and "/s" in unit):
        out[f"value[{unit}]"] = float(v)
    return out


def _lower_is_better(name: str) -> bool:
    return name in LATENCY_METRICS


def _config_key(payload: dict) -> tuple:
    return tuple(
        (f, repr(payload[f])) for f in CONFIG_FIELDS if f in payload
    )


def collect(paths: list[str]) -> tuple[dict, list[str]]:
    """Load + validate receipts; group by (kind, config fingerprint).

    Returns ``(groups, skipped)``: ``groups`` maps the key to the
    round-ordered list of ``{path, round, metrics}`` records (files
    without any gated metric are dropped — COPYCHECK.json and friends
    are receipts of a different trade); ``skipped`` names files that
    failed validation, for the report."""
    groups: dict[tuple, list[dict]] = {}
    skipped: list[str] = []
    for path in paths:
        try:
            obj = load_receipt(path)
        except (OSError, json.JSONDecodeError):
            skipped.append(f"{path}: unreadable/not JSON")
            continue
        problems = validate_receipt(obj)
        if problems:
            skipped.append(f"{path}: {problems[0]}")
            continue
        payload = _payload(obj)
        metrics = _metrics(payload)
        if not metrics:
            continue  # a valid receipt with nothing this gate watches
        key = (_kind(obj, path), _config_key(payload))
        groups.setdefault(key, []).append({
            "path": path,
            "round": _round(path),
            "metrics": metrics,
        })
    for recs in groups.values():
        recs.sort(key=lambda r: (r["round"], r["path"]))
    return groups, skipped


def check(groups: dict, tolerance: float) -> list[dict]:
    """Regressions: for every key/metric with >= 2 rounds, the LATEST
    round must reach ``(1 - tolerance) *`` the best earlier round —
    or, for the lower-is-better latency tails, stay within
    ``(1 + tolerance) *`` the best (lowest) earlier round."""
    regressions = []
    for (kind, cfg), recs in groups.items():
        if len(recs) < 2:
            continue
        latest = recs[-1]
        for name, value in latest["metrics"].items():
            earlier = [
                r["metrics"][name] for r in recs[:-1]
                if name in r["metrics"]
            ]
            if not earlier:
                continue
            if _lower_is_better(name):
                best = min(earlier)
                bad = value > best * (1.0 + tolerance)
                drop = value / best - 1.0 if best > 0 else 0.0
            else:
                best = max(earlier)
                bad = value < best * (1.0 - tolerance)
                drop = 1.0 - value / best
            if bad:
                regressions.append({
                    "kind": kind,
                    "config": dict(cfg),
                    "metric": name,
                    "direction": (
                        "lower" if _lower_is_better(name) else "higher"
                    ),
                    "best_earlier": best,
                    "latest": value,
                    "latest_path": latest["path"],
                    "drop": drop,
                })
    return regressions


def _print_table(groups: dict, regressions: list[dict]) -> None:
    bad = {(r["kind"], r["metric"], r["latest_path"]) for r in regressions}
    for (kind, cfg), recs in sorted(groups.items(), key=str):
        desc = " ".join(f"{k}={v}" for k, v in cfg) or "(no config fields)"
        print(f"{kind}  {desc}")
        names = sorted({n for r in recs for n in r["metrics"]})
        for name in names:
            traj = [
                (r["round"], r["metrics"][name], r["path"])
                for r in recs if name in r["metrics"]
            ]
            line = " -> ".join(
                f"r{rd:02d} {v:g}" if rd >= 0 else f"{v:g}"
                for rd, v, _ in traj
            )
            arrow = " (lower is better)" if _lower_is_better(name) else ""
            status = ""
            if len(traj) == 1:
                status = "  (single round)"
            elif (kind, name, traj[-1][2]) in bad:
                status = "  REGRESSION"
            print(f"  {name}{arrow}: {line}{status}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="fail when the newest receipt round regresses"
    )
    ap.add_argument("paths", nargs="*",
                    help="receipt files or directories (default: repo root)")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="allowed fractional drop vs best earlier round")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    args = ap.parse_args(argv)
    if not 0.0 <= args.tolerance < 1.0:
        print("--tolerance must be in [0, 1)", file=sys.stderr)
        return 2

    paths: list[str] = []
    roots = args.paths or [
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    ]
    for p in roots:
        if os.path.isdir(p):
            paths.extend(sorted(glob.glob(os.path.join(p, "*.json"))))
        else:
            paths.append(p)

    groups, skipped = collect(paths)
    regressions = check(groups, args.tolerance)
    if args.json:
        print(json.dumps({
            "tolerance": args.tolerance,
            "n_files": len(paths),
            "n_groups": len(groups),
            "skipped": skipped,
            "regressions": regressions,
        }, indent=2, sort_keys=True))
    else:
        _print_table(groups, regressions)
        for s in skipped:
            print(f"skipped {s}")
        for r in regressions:
            cmp = ">" if r.get("direction") == "lower" else "<"
            print(
                f"REGRESSION {r['kind']}.{r['metric']}: "
                f"{r['latest']:g} {cmp} best {r['best_earlier']:g} "
                f"({100 * r['drop']:+.1f}%, tolerance "
                f"{100 * args.tolerance:.1f}%) [{r['latest_path']}]"
            )
        print(f"{len(groups)} trajectories, {len(regressions)} regressions")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings for a cell's limits, on the chip at the cell's own size, several
seeds in one process:

    python3 benchmark/tests/chip_readings.py --workload <cell> --seeds 1,2,3 [--seconds 8]

For each seed it drives the timed path as a run does (a short window), then
reads, against the float32 reference: the program's numbers (the lower
reading), the control's (the reference put in the program's place one
precision lower) and, for a training cell, the half-batch fault planted in
the reference put in the program's place (and, on several chips, the exchange
left out: one chip's rows alone). One JSON line a seed. The
benchmark's own runs never call this; ``test_control.py`` keeps the same
comparison at a toy size.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()

    from benchmark import run
    from benchmark.lib import harness, serve_kind, train_kind

    def decide(cell, args, bundle, checks):
        proof = bundle["proof"]
        out = {}
        if bundle["kind"] == "train_steps":
            common = (bundle["block"], bundle["shape"], args.seed, proof["std"],
                      proof["batches"], proof["hyper"])
            ref = train_kind.reference_steps(*common)
            sides = {"program": proof["ours"]}
            half = list(range(len(proof["batches"][0][0]) // 2))
            arms = [("control_int8", {"precision": "int8"}),
                    ("control_float8", {"precision": "float8"}),
                    ("fault_half_batch", {"rows": half})]
            if bundle["counters"]["n_devices"] > 1:
                # the exchange between chips left out: each chip's own rows
                # alone, here the first chip's
                own = len(proof["batches"][0][0]) // bundle["counters"]["n_devices"]
                arms.append(("fault_no_exchange", {"rows": list(range(own))}))
            for name, kw in arms:
                r = train_kind.reference_steps(*common, **kw)
                sides[name] = {"losses": r[0], "grad_norms": r[1], "change_norms": r[2]}
            for name, ours in sides.items():
                d = train_kind.compare(harness.Checks(), {}, ours, ref)
                out[name] = dict(
                    d["gaps"], grad_at=d["grad_worst_leaf"],
                    change_at=d["change_worst_leaf"],
                    **{f"loss_step{i}_rel": abs(a - b) / abs(b) for i, (a, b)
                       in enumerate(zip(ours["losses"], ref[0]), 1)})
            out["losses"] = {"program": proof["ours"]["losses"], "reference": ref[0]}
            out["leaves_left_out"] = d["leaves_left_out"]
        else:
            common = (bundle["block"], bundle["shape"], proof["ref_params"],
                      proof["served"], cell.config["serve"]["window"])
            top = cell.traffic["output"]["max"]
            gaps, n = serve_kind.token_gaps(*common, max_out=top)
            low, _ = serve_kind.token_gaps(*common, weight_bits=4, max_out=top)
            out = {"program": {"served_token_gap": max(gaps), "each": gaps},
                   "control_int4": {"served_token_gap": max(low), "each": low},
                   "tokens_compared": n}
        checks.at_most("readings_only", 0, 0)
        return out

    for seed in (int(s) for s in opts.seeds.split(",")):
        args = argparse.Namespace(
            workload=opts.workload, seed=seed, seconds=opts.seconds, trace=0,
            rehearse=opts.rehearse, root=ROOT, dump_trace=None, mix=[],
            t_process_start=time.perf_counter())
        line = run.run_cell(args, control=decide)
        print(json.dumps({"seed": seed, "cell": opts.workload,
                          "readings": line["detail"]["reference"],
                          "failed": line["failed"], "device": line["device"]}),
              flush=True)
        del line  # the next seed needs the chip's memory
        import gc

        import jax

        gc.collect()
        jax.clear_caches()


if __name__ == "__main__":
    main()

"""One cell of ``BENCHMARK.json``, once:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. Refuses to measure unless JAX sees the TPU the cell asks for
(exit code 3, no result line). Builds the model from the configuration's
file, makes weights and inputs on the device from ``--seed``, warms the
shapes the cell's traffic uses (set-up), measures for ``--seconds``, frees
the program's state, runs the plain reference and prints the contract's
last line. ``--trace 1`` traces a few seconds of the window and reports the
cell's per-layer metrics in place of the end-to-end ones.

``--rehearse`` is for the tests under ``benchmark/tests``: toy widths, the
CPU allowed and named as the device, counts only.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=ROOT, help=argparse.SUPPRESS)
    ap.add_argument("--dump-trace", default=None, help=argparse.SUPPRESS)
    # one parameter of the traffic mix, for the sweep that finds a knee
    ap.add_argument("--mix", action="append", default=[], help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    args.t_process_start = T_PROCESS_START
    return args


def run_cell(args, fault=None, control=None) -> dict:
    """The whole run but the device gate's refusal; returns the result
    line as a dict. ``fault`` breaks the timed path underneath and
    ``control`` swaps the comparison's reference side (the tests)."""
    from benchmark.lib import harness

    cell = harness.Cell(args.workload, args.root)
    for item in getattr(args, "mix", []):
        key, _, value = item.partition("=")
        cell.traffic[key] = json.loads(value)
    if args.rehearse:
        harness.rehearsal_sizes(cell)
        if cell.chips > 1:
            os.environ.setdefault(
                "XLA_FLAGS", f"--xla_force_host_platform_device_count={cell.chips}"
            )

    from benchmark.lib import program, serve_kind, train_kind

    if not args.rehearse:
        program.enable_compile_cache(harness.CACHE_DIR)
    device = harness.gate_device(cell, args.rehearse)
    log = harness.CompileLog()
    kind = train_kind if cell.traffic["kind"] == "train_steps" else serve_kind
    # the traced part is the end of the window: the profiler stops after the
    # window has closed, so only its start can stall the measured path
    traced = min(cell.traffic.get("trace", {}).get("seconds", 3.0), args.seconds / 2)
    tracer = harness.Tracer(bool(args.trace), args.seconds - traced)
    bundle = kind.run(cell, args, log, tracer, fault=fault)
    bundle.update(cell=cell, device=device, peaks=cell.peaks["devices"].get(
        device["kind"]), root=args.root)

    # the reference, once the window has closed and the peak has been read
    checks = harness.Checks()
    decide = control or kind.decide
    detail = decide(cell, args, bundle, checks)
    checks.at_most("window_builds", bundle["counters"]["window_builds"], 0)
    checks.at_most("failed", bundle["failed"], 0)

    device_out = dict(device, memory_peak_bytes=bundle["memory_peak_bytes"])
    values = {"setup_s": bundle["setup_s"], **bundle["end_to_end"]}
    result = {
        "correct": checks.ok, "attempted": bundle["attempted"],
        "failed": bundle["failed"],
    }
    if args.trace:
        from benchmark.lib import xplane

        trace = None
        if tracer.t_stop is not None:
            trace = xplane.load(xplane.newest_xplane(harness.TRACE_DIR))
        bundle.update(trace=trace, values=values)
        if args.dump_trace and trace is not None:
            from benchmark.lib import trace_dump

            trace_dump.write(trace, args.dump_trace)
        if trace is not None and trace.devices:
            lo, hi = xplane.window_of(trace)
            per = {n: xplane.busy_seconds(e, lo, hi) for n, e in trace.devices.items()}
            name = max(per, key=per.get)  # the busiest chip
            device_out["busy_s"] = sum(per.values()) / len(per)
            device_out["window_s"] = (hi - lo) / 1e9
            evs = trace.devices[name]
            breakdown = {
                "device_ops": xplane.top(xplane.sums_by(
                    evs, lo, hi, lambda e: xplane.short_name(e.name))),
                "idle_gaps": xplane.top(
                    xplane.attribute_gaps(xplane.gaps(evs, lo, hi), trace.spans)),
            }
            bundle.update(trace_window=(lo, hi), busiest=name)
        else:
            breakdown = None
        result["metrics"] = harness.read_layer_metrics(cell, bundle)
        if breakdown:
            result["breakdown"] = breakdown
    else:
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values
        }
    result["device"] = device_out
    if args.rehearse:
        # a CPU run gives counts and never a time, a rate or a share
        result["metrics"] = {k: {"value": None, "unit": v["unit"]}
                             for k, v in result["metrics"].items()}
        result["counts"] = {
            k: v for k, v in bundle["counters"].items()
            if isinstance(v, int)
        }
    result["detail"] = {
        "setup_s": bundle["setup_s"], "window_s": bundle["window_s"],
        "reference": detail,
        "counters": {k: v for k, v in bundle["counters"].items()
                     if k != "done_lengths"},
    }
    result["checks"] = checks.as_dict()
    checks.print()
    return result


def main(argv=None) -> None:
    args = parse(argv)
    result = run_cell(args)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

"""``selective_scan``'s share of its roofline over the traced part of the
serving window: for every kernel event named ``selective_scan`` (a prefill's
scan of one Mamba layer; a decode step's update is no kernel), the least
time the chip could take for its operands ``u`` (rows, positions, channels)
and ``A`` (states, channels) (``benchmark/kernels/selective_scan``: the
bytes bound it) over the time it took. The operations are float32 on the
vector unit, for which ``peaks.json`` has no row: they are held against the
bfloat16 peak, which only makes the bytes' bound the binding one. None
where the trace has no such kernel: a program without these layers, or one
that scans with ``lax.scan``."""

from benchmark.lib import harness, roofline, xplane


def read(bundle):
    trace = bundle.get("trace")
    if trace is None or not trace.devices or bundle["peaks"] is None:
        return None
    cost = harness.kernel_cost(bundle["root"], "selective_scan").cost
    lo, hi = bundle["trace_window"]
    pairs = []
    for call in xplane.custom_calls(trace.devices[bundle["busiest"]], lo, hi):
        ops = call.operands
        if "selective_scan" not in call.instruction or len(ops) < 3:
            continue
        if len(ops[0][1]) != 3 or len(ops[2][1]) != 2:
            continue
        (rows, positions, channels), (states, _) = ops[0][1], ops[2][1]
        bound, _ = roofline.bound_seconds(
            *cost(rows, positions, channels, states), bundle["peaks"], "bfloat16")
        pairs.append((call.event.seconds, bound))
    return xplane.roofline_share(pairs)

"""``int8_matmul`` reading a layer's weights in the scanned stack: the
calls ``lib/serve_metrics.int8_matmul_roofline`` cannot class, because a
scalar-prefetched layer index is their operand 0 where it looks for ``x``."""

from __future__ import annotations

from . import harness, roofline, xplane


def stacked_roofline(bundle):
    """Share of its roofline, over the traced window, of every kernel event
    named ``int8_matmul`` (and not ``grouped_...``) whose first operand is
    the ``s32[1]`` layer index: ``x`` (m, k) is the operand after it, the
    weight the ``s8`` operand, whose last dimension is n (its rows are all
    the layers', L * k: one layer's (k, n) is what the call reads, and what
    ``benchmark/kernels/int8_matmul`` bounds it by). None where no call has
    that form: a program that slices its layers' weights, as the parent's."""
    trace = bundle.get("trace")
    if trace is None or not trace.devices or bundle["peaks"] is None:
        return None
    cost = harness.kernel_cost(bundle["root"], "int8_matmul").cost
    lo, hi = bundle["trace_window"]
    pairs = []
    for call in xplane.custom_calls(trace.devices[bundle["busiest"]], lo, hi):
        ops = call.operands
        if "int8_matmul" not in call.instruction or "grouped" in call.instruction:
            continue
        weights = [o for o in ops if o[0] == "s8"]
        if len(ops) < 4 or ops[0] != ("s32", (1,)) or len(ops[1][1]) != 2 or not weights:
            continue
        (m, k), n = ops[1][1], weights[0][1][-1]
        bound, _ = roofline.bound_seconds(*cost(m, k, n), bundle["peaks"], "int8")
        pairs.append((call.event.seconds, bound))
    return xplane.roofline_share(pairs)

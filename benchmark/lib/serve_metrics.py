"""What the serving cells' per-layer readers share: one quantity, two
names where the cells it is read in report different end-to-end metrics."""

from __future__ import annotations

from . import harness, roofline, xplane


def slot_occupancy(bundle):
    """Mean share of the engine's slots in use, ``engine.active_slots``
    read after every ``step()`` of the window."""
    c = bundle["counters"]
    if not c.get("occupancy_n"):
        return None
    return 100.0 * c["occupancy_sum"] / (c["occupancy_n"] * c["n_slots"])


def mfu(bundle):
    """Operations the requests completed in the window needed (prompt and
    generated tokens through the layers, the head a generated token, causal
    attention), a second, over the peak of the type the configuration's
    file says the matrix products run in (``serve.matmul_dtype``)."""
    done = bundle["counters"].get("done_lengths")
    if not done or bundle["peaks"] is None:
        return None
    serve_flops = bundle["block"].reference.serve_flops
    ops = sum(serve_flops(bundle["shape"], p, n) for p, n in done)
    dtype = bundle["cell"].config["serve"]["matmul_dtype"]
    peak = bundle["peaks"]["flops_per_s"][dtype] * bundle["device"]["count"]
    return 100.0 * ops / bundle["window_s"] / peak


def int8_matmul_roofline(bundle):
    """``int8_matmul``'s share of its roofline over the traced window: for
    every kernel event with an int8 (k, n) weight operand, the least time
    the chip could take for x (m, k) against it
    (``benchmark/kernels/int8_matmul``: bytes bound at decode where m is the
    slot count, operations bound at prefill where m is the prompt bucket)
    over the time it took."""
    trace = bundle.get("trace")
    if trace is None or not trace.devices or bundle["peaks"] is None:
        return None
    cost = harness.kernel_cost(bundle["root"], "int8_matmul").cost
    lo, hi = bundle["trace_window"]
    pairs = []
    for call in xplane.custom_calls(trace.devices[bundle["busiest"]], lo, hi):
        ops = call.operands
        if len(ops) < 3 or ops[1][0] != "s8" or len(ops[1][1]) != 2:
            continue
        (m, k), (_, n) = ops[0][1], ops[1][1]
        bound, _ = roofline.bound_seconds(*cost(m, k, n), bundle["peaks"], "int8")
        pairs.append((call.event.seconds, bound))
    return xplane.roofline_share(pairs)

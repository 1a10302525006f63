"""``grouped_int8_matmul``'s share of its roofline over the traced part of
the serving window: for every kernel event with an int8 (experts, k, n)
weight operand, the least time the chip could take for the pairs the call
expects from its shapes (``benchmark/kernels/grouped_int8_matmul``: the
weights of the held experts that get a pair read once, which bounds a
decode call) over the time it took."""

from benchmark.lib import harness, roofline, xplane


def read(bundle):
    trace = bundle.get("trace")
    if trace is None or not trace.devices or bundle["peaks"] is None:
        return None
    config = bundle["cell"].config
    if "n_routed_experts_published" not in config:
        return None
    kernel = harness.kernel_cost(bundle["root"], "grouped_int8_matmul")
    lo, hi = bundle["trace_window"]
    pairs = []
    for call in xplane.custom_calls(trace.devices[bundle["busiest"]], lo, hi):
        weights = [o for o in call.operands if o[0] == "s8" and len(o[1]) == 3]
        tiles = [o for o in call.operands if o[0] == "s32" and len(o[1]) == 1]
        rows = [o for o in call.operands if o[0] in ("bf16", "f32") and len(o[1]) == 2]
        if not (weights and tiles and rows):
            continue
        experts, k, n = weights[0][1]
        m = rows[0][1][0]
        shapes = (m, tiles[0][1][0], experts, config["n_routed_experts_published"])
        item = 2 if rows[0][0] == "bf16" else 4
        bound, _ = roofline.bound_seconds(
            *kernel.cost(kernel.expected_pairs(*shapes),
                         kernel.expected_experts(*shapes), k, n, item, item),
            bundle["peaks"], "int8")
        pairs.append((call.event.seconds, bound))
    return xplane.roofline_share(pairs)

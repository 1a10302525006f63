"""``ssd_update``'s share of its roofline over the traced part of the
serving window: for every kernel event named ``ssd_update`` (a decode step's
state update of one Mamba-2 layer, in place on the carried stack), the least
time the chip could take for the call's own operands (``dtx`` (slots, heads,
head_dim), ``B`` (slots, groups, states, 1) and the stack (layers, slots,
heads, states, head_dim): ``benchmark/kernels/ssd_update``; the bytes bound
it: the state once in and once out) over the time it took. The shapes are
the call's operands', never the host's estimate; a slot that holds no live
sequence costs the kernel no traffic and is counted as if it did. The
operations are float32 on the vector unit, for which ``peaks.json`` has no
row: they are held against the bfloat16 peak, which only makes the bytes'
bound the binding one. None where the trace has no such kernel: a program
without these layers, as the parent's, or one that updates with the plain
step."""

from benchmark.lib import harness, roofline, xplane


def read(bundle):
    trace = bundle.get("trace")
    if trace is None or not trace.devices or bundle["peaks"] is None:
        return None
    cost = harness.kernel_cost(bundle["root"], "ssd_update").cost
    lo, hi = bundle["trace_window"]
    pairs = []
    for call in xplane.custom_calls(trace.devices[bundle["busiest"]], lo, hi):
        if "ssd_update" not in call.instruction:
            continue
        stacks = [dims for kind, dims in call.operands
                  if kind == "f32" and len(dims) == 5]
        columns = [dims for kind, dims in call.operands
                   if kind == "f32" and len(dims) == 4]
        if not (stacks and columns):
            continue
        _, slots, heads, states, head_dim = stacks[0]
        bound, _ = roofline.bound_seconds(
            *cost(slots, heads, head_dim, states, groups=columns[0][1]),
            bundle["peaks"], "bfloat16")
        pairs.append((call.event.seconds, bound))
    return xplane.roofline_share(pairs)

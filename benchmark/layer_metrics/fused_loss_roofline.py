"""``fused_cross_entropy``'s share of its roofline over the traced window.
Its kernels are the custom calls whose first two operands are the hidden
states (batch * seq, d) and the head (d, vocab padded to the kernel's
block) in bfloat16: three operands is the forward, more is the backward
pair dh / dW, together one backward (``benchmark/kernels/fused_loss``)."""

from benchmark.lib import harness, roofline, xplane


def read(bundle):
    trace = bundle.get("trace")
    if trace is None or not trace.devices or bundle["peaks"] is None:
        return None
    shape, mix = bundle["shape"], bundle["cell"].traffic
    rows = mix["batch"] * mix["seq_len"] // bundle["counters"]["n_devices"]
    d, vocab = shape.hidden_size, shape.vocab_size
    cost = harness.kernel_cost(bundle["root"], "fused_loss").cost
    args = dict(rows=rows, d=d, vocab=vocab, itemsize=2)
    fwd, _ = roofline.bound_seconds(*cost(**args), bundle["peaks"], "bfloat16")
    bwd, _ = roofline.bound_seconds(
        *cost(**args, backward=True), bundle["peaks"], "bfloat16")
    lo, hi = bundle["trace_window"]
    pairs = []
    for call in xplane.custom_calls(trace.devices[bundle["busiest"]], lo, hi):
        ops = call.operands
        if len(ops) < 3 or ops[0] != ("bf16", (rows, d)):
            continue
        if ops[1][0] != "bf16" or ops[1][1][0] != d or ops[1][1][1] < vocab:
            continue
        if len(ops) == 3:
            pairs.append((call.event.seconds, fwd))
        else:  # dh gives (rows, d), dW the head's shape: the pair is one backward
            is_dw = call.results[0][1][0] == d and call.results[0][1] != (rows, d)
            pairs.append((call.event.seconds, bwd if is_dw else 0.0))
    return xplane.roofline_share(pairs)

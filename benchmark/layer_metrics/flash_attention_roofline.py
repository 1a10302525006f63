"""``flash_attention``'s share of its roofline over the traced window.
Its kernels are the custom calls whose first three operands are
(batch * heads, seq, head_dim) bfloat16: three operands is a forward call
(the one remat repeats in the backward pass counts as a call of its own),
more is the backward pair dq / dkv, which together are one backward
(``benchmark/kernels/flash_attention``). The fused loss's ``_fwd_kernel``
has the same name in the program and other shapes."""

from benchmark.lib import harness, roofline, xplane


def read(bundle):
    trace = bundle.get("trace")
    if trace is None or not trace.devices or bundle["peaks"] is None:
        return None
    shape, mix = bundle["shape"], bundle["cell"].traffic
    b = mix["batch"] // bundle["counters"]["n_devices"]
    dims = (b * shape.num_attention_heads, mix["seq_len"], shape.head_dim)
    cost = harness.kernel_cost(bundle["root"], "flash_attention").cost
    args = dict(batch=b, heads=shape.num_attention_heads,
                kv_heads=shape.num_key_value_heads, seq=mix["seq_len"],
                head_dim=shape.head_dim, itemsize=2)
    fwd, _ = roofline.bound_seconds(*cost(**args), bundle["peaks"], "bfloat16")
    bwd, _ = roofline.bound_seconds(
        *cost(**args, backward=True), bundle["peaks"], "bfloat16")
    lo, hi = bundle["trace_window"]
    pairs = []
    for call in xplane.custom_calls(trace.devices[bundle["busiest"]], lo, hi):
        ops = call.operands
        if len(ops) < 3 or any(o != ("bf16", dims) for o in ops[:3]):
            continue
        if len(ops) == 3:
            pairs.append((call.event.seconds, fwd))
        else:  # dq has one result, dkv two: the pair is one backward
            pairs.append((call.event.seconds, bwd if len(call.results) == 2 else 0.0))
    return xplane.roofline_share(pairs)

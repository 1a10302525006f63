"""``latent_decode_attention``'s share of its roofline over the traced part
of the serving window: for every kernel event with a (layers, slots, window,
width) cache operand, the least time the chip could take for the rows the
slots needed on average (``benchmark/kernels/latent_decode_attention``) over
the time it took. A slot's depth is not in a call's shapes: the context is
an ESTIMATE from the host's side, the mean over the requests completed in
the window of prompt plus half the output, which a slot holds at its mean
(a closed loop keeps every slot busy), and not the depths the traced calls
ran at. The sizes of a cached token are the configuration's
(``kv_lora_rank``, ``qk_rope_head_dim``), not the stored row's: the padding
columns and the rows past a slot's depth that the kernel reads are charged
to it."""

from benchmark.lib import harness, roofline, xplane


def read(bundle):
    trace = bundle.get("trace")
    done = bundle["counters"].get("done_lengths")
    if trace is None or not trace.devices or bundle["peaks"] is None or not done:
        return None
    config = bundle["cell"].config
    if "kv_lora_rank" not in config:
        return None
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    context = sum(p + n / 2 for p, n in done) / len(done)
    cost = harness.kernel_cost(bundle["root"], "latent_decode_attention").cost
    dtype = config["serve"].get("compute_dtype", "bfloat16")
    lo, hi = bundle["trace_window"]
    pairs = []
    for call in xplane.custom_calls(trace.devices[bundle["busiest"]], lo, hi):
        caches = [o for o in call.operands if len(o[1]) == 4 and o[0] in ("bf16", "f32")]
        queries = [o for o in call.operands if len(o[1]) == 3 and o[0] in ("bf16", "f32")]
        if not (caches and queries):
            continue
        slots, heads, _ = queries[0][1]
        window = caches[0][1][2]
        item = 2 if caches[0][0] == "bf16" else 4
        bound, _ = roofline.bound_seconds(
            *cost(slots, heads, min(context, window), rank, rope, item),
            bundle["peaks"], dtype)
        pairs.append((call.event.seconds, bound))
    return xplane.roofline_share(pairs)

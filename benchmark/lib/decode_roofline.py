"""``decode_attention``'s share of its roofline, with the rows its calls
read counted by the program: what ``decode_attention_roofline.*`` read.

A call's shapes hold neither the slots' depths nor which slots are live,
so the rows come from ``ServeEngine``'s span ``prog:chain_dispatch``: its
``kv_rows`` are the rows one decode attention call a step reads from a
full-length cache, summed over the chain's ``tokens_per_launch`` (T) steps
and its live slots, ``ring_rows`` the same of a ring. A chain counts where
the trace holds both its dispatch and its fetch (joined by ``chain``) inside
the traced window; the ``decode_attention`` kernels of ``jit__chain_fn``
that start between the dispatch's start and the fetch's end are its calls
(at ``pipeline_depth`` 1 nothing else runs there), each charged ``kv_rows /
T`` rows, or ``ring_rows / T`` under the scope ``window_attn``. Everything
else is the call's own operands: q (slots, heads, head_dim), the K stack
(layers, slots, rows, KV heads, head_dim) or with a KV head's rows together
(layers, slots, KV heads, rows, head_dim), the result. None where no chain
carries the field (a program without the count, as the parent's) or no
call falls inside one: never 0, never an error.
"""

from __future__ import annotations

import re

from . import harness, program_trace, roofline, xplane

KERNEL = re.compile(r"^%?decode_attention(\.\d+)?$")  # not latent_...
RING_SCOPE = "window_attn"
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def calls(bundle) -> list | None:
    """[(call, rows it read, on a ring)] of the traced chains' decode
    attention; None where no chain in the window carries the count."""
    pt = program_trace.of(bundle)
    found = program_trace._busiest(pt, bundle)
    if found is None or bundle.get("cell") is None:
        return None
    ops, lo, hi = found
    steps = bundle["cell"].config["serve"]["engine"].get("tokens_per_launch", 8)
    chains = []
    for sp in program_trace.joined(
            pt, "chain", ("chain_dispatch", "chain_fetch")).values():
        d, f = sp.get("chain_dispatch"), sp.get("chain_fetch")
        if d and f and "kv_rows" in d.fields and lo <= d.start and f.end <= hi:
            chains.append((d.start, f.end, d.fields))
    if not chains:
        return None
    mine = [o for o in ops if o.program and "_chain_fn" in o.program]
    out = []
    for call in xplane.custom_calls(mine, lo, hi):
        if not KERNEL.match(call.instruction):
            continue
        fields = [c[2] for c in chains if c[0] <= call.event.start <= c[1]]
        if not fields:
            continue
        ring = RING_SCOPE in program_trace.scopes_on(call.event.path)
        field = fields[0].get("ring_rows" if ring else "kv_rows")
        if field is not None:
            out.append((call, field / steps, ring))
    return out


def share(bundle, ring: bool | None = None) -> float | None:
    """Percent: the least time the chip could take for the rows the
    matched calls read over the time they took. ``ring`` True / False
    keeps the calls on a ring / on a full-length cache alone."""
    found = calls(bundle) if bundle.get("peaks") is not None else None
    if not found:
        return None
    cost = harness.kernel_cost(bundle["root"], "decode_attention").cost
    pairs = []
    for call, rows, on_ring in found:
        if ring is not None and on_ring != ring:
            continue
        qs = [o for o in call.operands if len(o[1]) == 3 and o[0] in _BYTES]
        stacks = [o for o in call.operands if len(o[1]) == 5 and o[0] in _BYTES]
        if not (qs and stacks and call.results):
            continue
        (q_type, (slots, heads, head_dim)), (kv_type, dims) = qs[0], stacks[0]
        # rows or KV heads third: the rows are the longer axis (a block of
        # them is 128 or more, the KV heads of one are at most 32)
        kv_heads = min(dims[2], dims[3])
        bound, _ = roofline.bound_seconds(
            *cost(rows, slots, heads, kv_heads, head_dim, _BYTES[kv_type],
                  _BYTES[q_type], _BYTES.get(call.results[0][0], 4)),
            bundle["peaks"], "bfloat16")
        pairs.append((call.event.seconds, bound))
    return xplane.roofline_share(pairs)

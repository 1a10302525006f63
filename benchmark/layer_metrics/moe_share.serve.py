"""Share of the busiest chip's busy time spent in the expert layers' own
work: the operations under any of the program's scopes ``moe_router``
(scores, top-k, weights), ``moe_dispatch`` (sorting rows by expert,
gathering them, combining the results), ``moe_experts`` (the grouped
products) and ``moe_shared`` (the shared expert). No operation lies under
two of them and a chip runs one operation at a time, so the scopes' shares
add up to the share of their union."""

from benchmark.lib import scope_share

SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_shared")


def read(bundle):
    shares = [scope_share.under(bundle, s) for s in SCOPES]
    return sum(s for s in shares if s is not None) if any(shares) else None

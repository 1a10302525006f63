"""Share of the busiest chip's busy time that routing costs beside the
products it feeds: the operations under the program's scopes ``moe_router``
(scores over all experts, top-k, weights) and ``moe_dispatch`` (the plan,
the gather of rows sorted by expert, the weighted combine), added up as
``moe_share.serve`` adds its four."""

from benchmark.lib import scope_share


def read(bundle):
    shares = [scope_share.under(bundle, s) for s in ("moe_router", "moe_dispatch")]
    return sum(s for s in shares if s is not None) if any(shares) else None

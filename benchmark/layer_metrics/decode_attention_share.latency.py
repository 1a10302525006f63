"""Share of the busiest chip's busy time spent in decode attention
(operations under the program's scope ``decode_attn``): the
``decode_attention`` kernel that reads K and V where they lie in the
carried stack, up to each live slot's depth, and the few integer operations
that tell it which blocks a slot needs. The projections around it are
``int8_matmul``'s; the new rows' scatter is ``kv_cache_share.*``."""

from benchmark.lib import scope_share


def read(bundle):
    return scope_share.under(bundle, "decode_attn")

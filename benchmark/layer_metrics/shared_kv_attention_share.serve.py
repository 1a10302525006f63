"""Share of the busiest chip's busy time spent in attention over the ONE cache of
K and V that layer ``half + 1`` writes (the operations under the program's
scope ``shared_kv_attn``): that layer's own attention and the cross-attention
layers', a decode step's ``decode_attention`` calls up to each slot's depth
and prefill's one position. None where the trace has no such scope: a program
without these layers, as the parent's."""

from benchmark.lib import scope_share


def read(bundle):
    return scope_share.under(bundle, "shared_kv_attn")

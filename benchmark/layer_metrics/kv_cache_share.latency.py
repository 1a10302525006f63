"""Share of the busiest chip's busy time spent on the KV cache itself, outside
the attention that reads it (operations under the program's scope
``kv_cache``): the new rows written into the cache, and each layer's K and V
taken out of the stacked cache as a copy before the attention reads them."""

from benchmark.lib import scope_share


def read(bundle):
    return scope_share.under(bundle, "kv_cache")

"""Share of the busiest chip's busy time spent in latent attention itself
(operations under the program's scope ``latent_attn``): at decode the
absorbed query, the scores and the values over the latent cache, the
un-absorbed output; at prefill the up-projection of K and V and the
attention over them. The projections around it are ``int8_matmul``'s; the
cache's writes and read copies are ``kv_cache_share.*``."""

from benchmark.lib import scope_share


def read(bundle):
    return scope_share.under(bundle, "latent_attn")

"""Share of the busiest chip's busy time spent in a prefill's causal
attention (operations under the program's scope ``prefill_attn``): the
``flash_attention_fwd`` kernel with its layout copies where the program's
rule picks it, the dense form's scores, softmax and context product (and
the copy of K and V to the query head count) where it does not. The
projections around it are ``int8_matmul``'s; the cache's write is
``kv_cache_share.*``. A program without the scope reads nothing."""

from benchmark.lib import scope_share


def read(bundle):
    return scope_share.under(bundle, "prefill_attn")

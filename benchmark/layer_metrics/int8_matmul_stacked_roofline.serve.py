"""``int8_matmul``'s share of its roofline over the traced part of the
serving window, for the calls under the layer scan: they read their layer's
weights where they lie in the stacked parameters, at a scalar-prefetched
layer index, and each is bounded by one layer's bytes and operations
(bytes-bound decode calls and operations-bound prefill calls mixed by
time). ``int8_matmul_roofline*`` reads the calls on a (k, n) weight: the
head, and every layer of an unrolled model."""

from benchmark.lib import int8_stacked


def read(bundle):
    return int8_stacked.stacked_roofline(bundle)

"""``decode_attention``'s share of its roofline over the traced chains: for
every call of the kernel in ``jit__chain_fn`` inside a chain whose
``prog:chain_dispatch`` and ``prog:chain_fetch`` the trace holds, the least
time the chip could take for the rows the program counted at that dispatch
(``kv_rows``, or ``ring_rows`` for a window layer's ring, over the chain's
steps: ``benchmark/lib/decode_roofline``, ``benchmark/kernels/
decode_attention``) over the time it took. None on a trace without the
count, as the parent's."""

from benchmark.lib import decode_roofline


def read(bundle):
    return decode_roofline.share(bundle)

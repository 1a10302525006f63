"""Share of the busiest chip's busy time spent in the window layers' attention
(the operations under the program's scope ``window_attn``): a decode step's
``decode_attention`` over each layer's ring of the newest rows, prefill's
banded attention over the prompt. None where the trace has no such scope: a
program without these layers, as the parent's."""

from benchmark.lib import scope_share


def read(bundle):
    return scope_share.under(bundle, "window_attn")

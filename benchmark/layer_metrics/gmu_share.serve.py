"""Share of the busiest chip's busy time spent in the Gated Memory Units (the
operations under the program's scope ``gmu``): the two products and the gate
on layer ``half``'s scan output. None where the trace has no such scope: a
program without these layers, as the parent's."""

from benchmark.lib import scope_share


def read(bundle):
    return scope_share.under(bundle, "gmu")

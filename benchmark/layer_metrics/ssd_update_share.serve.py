"""Share of the busiest chip's busy time spent in the kernel named
``ssd_update`` (a ``pallas_call``'s ``name=`` is the last scope on its
event's path: ``.../mamba/ssm_scan/ssd_update/pallas_call``): a decode step's
update of one Mamba-2 layer's state where it lies in the carried stack
(``ops/ssd.py``), 4 MB read and 4 MB written a live slot a layer at
Falcon-H1-34B's sizes. What is around it under the scope ``ssm_scan`` (the
decay, ``dt x``) and prefill's chunked form are ``ssm_share.serve``'s. None
where the trace has no such kernel: a program without these layers, as the
parent's."""

from benchmark.lib import scope_share


def read(bundle):
    return scope_share.under(bundle, "ssd_update")

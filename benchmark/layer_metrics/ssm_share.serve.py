"""Share of the busiest chip's busy time spent in the Mamba layers' own work:
the operations under the program's scopes ``ssm_conv`` (the causal depthwise
convolution and its tail) and ``ssm_scan`` (prefill's selective scan over a
prompt, a decode step's state update). The projections around them are
``int8_matmul``'s; the state's and the tail's writes into the slot tree are
``kv_cache_share.*`` (a step's update fuses into that write). No operation
lies under both scopes and a chip runs one operation at a time, so the
scopes' shares add up to their union's. None where the trace has no such
scope: a program without these layers, as the parent's."""

from benchmark.lib import scope_share

SCOPES = ("ssm_conv", "ssm_scan")


def read(bundle):
    shares = [scope_share.under(bundle, s) for s in SCOPES]
    return sum(s for s in shares if s is not None) if any(shares) else None

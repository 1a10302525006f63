"""Share of the traced window the busiest chip spent in collective
operations (all-gather, reduce-scatter, all-reduce, all-to-all,
collective-permute and their fusions), classed by the HLO instruction the
trace names each event with."""

import re

from benchmark.lib import xplane

_COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")


def read(bundle):
    trace = bundle.get("trace")
    if trace is None or not trace.devices:
        return None
    lo, hi = bundle["trace_window"]
    evs = [e for e in trace.devices[bundle["busiest"]]
           if _COLLECTIVE.match(e.name) or " all-gather(" in e.name[:200]
           or " all-reduce(" in e.name[:200] or " reduce-scatter(" in e.name[:200]]
    if not evs:
        return None
    return 100.0 * xplane.busy_seconds(evs, lo, hi) / ((hi - lo) / 1e9)

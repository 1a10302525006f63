"""Mean share of the engine's slots in use over the window."""

from benchmark.lib import serve_metrics


def read(bundle):
    return serve_metrics.slot_occupancy(bundle)

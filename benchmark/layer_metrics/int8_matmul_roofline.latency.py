"""``int8_matmul``'s share of its roofline over the traced part of the
serving window (bytes-bound decode calls and operations-bound prefill calls
mixed by time)."""

from benchmark.lib import serve_metrics


def read(bundle):
    return serve_metrics.int8_matmul_roofline(bundle)

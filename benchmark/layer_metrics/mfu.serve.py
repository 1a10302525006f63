"""The served requests' analytic operations a second over the chip's peak
of the type the matrix products run in."""

from benchmark.lib import serve_metrics


def read(bundle):
    return serve_metrics.mfu(bundle)

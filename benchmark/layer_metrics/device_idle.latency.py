"""Idle share of the busiest chip over the traced part of the serving window."""

from benchmark.lib import xplane


def read(bundle):
    return xplane.idle_share(bundle)

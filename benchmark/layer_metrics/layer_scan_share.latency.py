"""Share of the busiest chip's busy time spent in what ``lax.scan`` itself
does around the scanned layer: taking each layer's weights and its cache
slice out of the stacked arrays and stacking the new cache back (operations
under the program's scope ``layer_scan`` and not under its cell ``layers``)."""

from benchmark.lib import program_trace


def read(bundle):
    return program_trace.scan_slicing_share(bundle)

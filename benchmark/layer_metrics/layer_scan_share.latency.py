"""Share of the busiest chip's busy time spent in what ``lax.scan`` itself
does around the scanned layer: taking each layer's weights out of the
stacked arrays (operations under the program's scope ``layer_scan`` and not
under its cell ``layers``). Since PR 28 the scan carries the cache, whose
reads and writes are the program's own lines: ``kv_cache_share.*``."""

from benchmark.lib import program_trace


def read(bundle):
    return program_trace.scan_slicing_share(bundle)

"""Share of the busiest chip's busy time spent inside the prefill programs
(the operations whose program, named by the trace's ``XLA Modules`` line, is a
``_prefill_fn``)."""

from benchmark.lib import program_trace


def read(bundle):
    return program_trace.program_share(bundle, "_prefill_fn")

"""What the host costs the chip an engine step: milliseconds of a
``prog:step`` span in which the busiest chip ran nothing, mean over the
traced steps, the launch and hand-over waits inside ``chain_fetch`` and
``prefill_fetch`` included (``pipeline_depth`` 1: the chip waits it out)."""

from benchmark.lib import program_trace


def read(bundle):
    return program_trace.step_host_ms(bundle)

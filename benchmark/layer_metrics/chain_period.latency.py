"""Mean pause a streaming client sees between bursts of ``tokens_per_launch``
tokens (a chain hands all of them over at once): from the end of the traced
window's first ``prog:chain_fetch`` span to the end of its last, over the
chains between them as the spans' ``chain`` fields count them."""

from benchmark.lib import program_trace


def read(bundle):
    return program_trace.chain_period_ms(bundle)

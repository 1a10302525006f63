"""Share of the traced window the training loop spent inside
``prog:loader_next``: the ``next()`` that ``Trainer`` itself makes on its
loader, where it consumes the batch."""

from benchmark.lib import program_trace


def read(bundle):
    return program_trace.span_share(bundle, "loader_next")

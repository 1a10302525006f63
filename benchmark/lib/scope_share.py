"""Share of the busiest chip's busy time under one of the program's named
scopes (``jax.named_scope``, read from an operation's ``tf_op`` path): what a
``<scope>_share.*`` reader under ``benchmark/layer_metrics/`` calls."""

from benchmark.lib import program_trace


def under(bundle, scope: str) -> float | None:
    """Percent of busy time taken by the operations whose path holds
    ``scope``; None where the trace has none (or there is no trace)."""
    return program_trace.share_of_busy(
        bundle, lambda o: scope in program_trace.scopes_on(o.path))

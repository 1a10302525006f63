"""Seconds of set-up spent tracing, lowering and compiling programs or
reading them back from the persistent cache (``jax.monitoring`` duration
events under ``/jax/core/compile/``), up to the window's opening."""


def read(bundle):
    return bundle["counters"]["setup_compile"]["compile_s"]

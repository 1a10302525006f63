"""JAX's persistent compilation cache at a path that can be placed from
outside.

Every entry point (``chip_smoke.py``, ``bench.py``, the ``serve``/``obs``
CLIs, the bench and example scripts) calls :func:`enable_compile_cache`
first thing. Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it
itself and this module sets no directory; where it is not, the cache
lives at ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because
the path is part of what a cache hit depends on across processes, so a
directory made from ``tempfile``, a pid or the time would never hit.
A run forced onto the CPU (``JAX_PLATFORMS=cpu``, the test path) gets no
default directory: nothing it compiles is what a user waits for, and
XLA:CPU's loader logs a page of warnings for every cached program it
reads back.

Importing this module touches no backend (``tests/test_import_purity``).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on; returns the directory
    in use (None: a CPU-forced run with no directory given). Call before
    the first compilation."""
    import jax

    # every program is worth keeping: a serving warm-up is dozens of
    # sub-second compiles that the default 1 s floor would all drop
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        return None
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

"""``shard_map`` with the varying-mesh-axes check off: one spelling for the
bodies the checker cannot follow.

No jax arrays are created at import time (CLAUDE.md import-purity rule).
"""

from __future__ import annotations

import jax


def shard_map_nocheck(f, mesh, in_specs, out_specs):
    """``jax.shard_map(..., check_vma=False)``. For bodies whose outputs
    carry no varying-axes info the checker can follow (``pallas_call``
    custom calls, unrolled ppermute rings)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )

"""Tiny pytree helpers shared across strategy/checkpoint modules.

Kept dependency-free (no orbax/flax imports) so hot-path modules can use it
without dragging in heavyweight packages.
"""

from __future__ import annotations


def keystr(key_path) -> str:
    """'block/attn/kernel'-style path string from a
    ``jax.tree_util.tree_map_with_path`` key path."""
    return "/".join(
        str(getattr(k, "key", getattr(k, "idx", k))) for k in key_path
    )


def device_materialize(tree):
    """Rewrite every array leaf as the OUTPUT of an on-device computation
    (a jitted exact identity: ``leaf + zeros((), dtype)``).

    Why this exists: checkpoint restores without an explicit sharding
    land leaves as HOST NUMPY (``parallel.auto.restore_leaf`` — by design,
    to keep host peak one-leaf-bounded), and jit re-uploads numpy
    arguments on EVERY call — the whole tree's bytes per launch (what that
    costs on the chip: not measured). After this one-time pass the leaves
    are device buffers, values bit-identical.

    Safe anywhere: a single fused launch for the whole tree, exact for
    every dtype (+0 in the leaf's own dtype), and jit's default sharding
    propagation preserves each leaf's placement (replicated or
    NamedSharding'd trees come back placed the same way). It costs one
    pass of device memory bandwidth and changes nothing else. Non-array
    leaves pass through untouched.
    """
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    is_arr = [hasattr(l, "dtype") and hasattr(l, "ndim") for l in leaves]
    arrays = [l for l, a in zip(leaves, is_arr) if a]
    if arrays:
        arrays = jax.jit(
            lambda ls: [l + jnp.zeros((), l.dtype) for l in ls]
        )(arrays)
        arrays = jax.block_until_ready(arrays)
    it = iter(arrays)
    out = [next(it) if a else l for l, a in zip(leaves, is_arr)]
    return jax.tree_util.tree_unflatten(treedef, out)

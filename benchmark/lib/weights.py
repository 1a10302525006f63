"""Weights from the seed, made on the device in one jitted call, in the
type they are used in, in the layout of the block's reference: any tree of
``(dims, kind)`` that its ``leaf_shapes(shape)`` gives.

``float32``: every matrix and the embedding ``normal(0, initializer_range)``
(the published ``initializer_range``), norms 1: what a training run starts
from. ``int8``: each matrix is int8 values uniform in [-127, 127] with one
float32 scale a column, jittered by a quarter around the scale that gives
the column the same ``initializer_range`` deviation, so a path that drops
or mixes up scales shows; embedding and norms float32. The float32 model of
a 7 B configuration (29 GB) never exists.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_UNIFORM_INT8_STD = 127 / 3**0.5  # deviation of uniform [-127, 127]


def seed_key(seed: int):
    """A PRNG key from any whole number (``--seed`` may pass 2**31)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _flat(spec: dict, parents: tuple = ()):
    """(path, leaf) of every leaf of a tree of nested dicts."""
    for name, v in spec.items():
        if isinstance(v, dict):
            yield from _flat(v, parents + (name,))
        else:
            yield parents + (name,), v


def n_params(spec: dict) -> int:
    total = 0
    for _, (dims, _) in _flat(spec):
        n = 1
        for s in dims:
            n *= s
        total += n
    return total


def _leaf(key, shp, kind, dtype, std):
    if kind == "norm":
        return jnp.ones(shp, jnp.float32)
    if kind == "embed" or dtype == "float32":
        return std * jax.random.normal(key, shp, jnp.float32)
    kq, ks = jax.random.split(key)
    q = jax.lax.bitcast_convert_type(
        jax.random.bits(kq, shp, jnp.uint8), jnp.int8
    )
    q = jnp.where(q == jnp.int8(-128), jnp.int8(-127), q)
    jitter = jax.random.uniform(
        ks, shp[:-2] + (1, shp[-1]), jnp.float32, 0.75, 1.25
    )
    return {"q": q, "scale": jitter * (std / _UNIFORM_INT8_STD)}


def build(spec: dict, key, dtype: str, std: float):
    """The whole tree of a block's ``leaf_shapes`` (trace this under
    ``jax.jit``). A leaf's key is folded by its place in the sorted
    ``(group, name)`` pairs, ``group`` the path of the dicts above it
    (``""`` at the top), so a seed's weights do not move while a block's
    names stay."""
    out: dict = {}
    flat = sorted(_flat(spec), key=lambda item: ("/".join(item[0][:-1]), item[0][-1]))
    for i, (path, (shp, kind)) in enumerate(flat):
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = _leaf(jax.random.fold_in(key, i), shp, kind, dtype, std)
    return out


def make(spec: dict, seed: int, dtype: str, std: float, convert=None,
         out_shardings=None):
    """One jitted call from the seed. ``convert`` maps the tree to another
    layout inside the same program (the program's own names and shapes);
    ``out_shardings`` places its leaves."""
    def fn(key):
        tree = build(spec, key, dtype, std)
        return tree if convert is None else convert(tree)

    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    return jax.jit(fn, **kw)(seed_key(seed))

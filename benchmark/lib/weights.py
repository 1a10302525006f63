"""Weights from the seed, made on the device in one jitted call, in the
type they are used in, in ``benchmark/reference.py``'s layout.

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


def leaf_shapes(shape) -> dict:
    """name -> (shape, kind) with kind ``matrix``, ``norm`` or ``embed``."""
    d, ff, L = shape.hidden_size, shape.intermediate_size, shape.num_hidden_layers
    q = shape.num_attention_heads * shape.head_dim
    kv = shape.num_key_value_heads * shape.head_dim
    layers = {
        "attn_norm": ((L, d), "norm"),
        "wq": ((L, d, q), "matrix"),
        "wk": ((L, d, kv), "matrix"),
        "wv": ((L, d, kv), "matrix"),
        "wo": ((L, q, d), "matrix"),
        "mlp_norm": ((L, d), "norm"),
        "w_gate": ((L, d, ff), "matrix"),
        "w_up": ((L, d, ff), "matrix"),
        "w_down": ((L, ff, d), "matrix"),
    }
    return {
        "embed": ((shape.vocab_size, d), "embed"),
        "layers": layers,
        "final_norm": ((d,), "norm"),
        "head": ((d, shape.vocab_size), "matrix"),
    }


def seed_key(seed: int):
    """A PRNG key from any whole number (``--seed`` may pass 2**31)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def n_params(shape) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(
        leaf_shapes(shape), is_leaf=lambda x: isinstance(x, tuple)
    ):
        n = 1
        for s in leaf[0]:
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


def build(shape, key, dtype: str, std: float):
    """The whole tree (trace this under ``jax.jit``)."""
    spec = leaf_shapes(shape)
    flat = {("", k): v for k, v in spec.items() if k != "layers"}
    flat.update({("layers", k): v for k, v in spec["layers"].items()})
    out = {"layers": {}}
    for i, ((group, name), (shp, kind)) in enumerate(sorted(flat.items())):
        leaf = _leaf(jax.random.fold_in(key, i), shp, kind, dtype, std)
        (out["layers"] if group else out)[name] = leaf
    return out


def make(shape, seed: int, dtype: str, std: float, convert=None,
         out_shardings=None):
    """One jitted call from the seed. ``convert`` maps the tree to another
    layout inside the same program (the program's own names and shapes);
    ``out_shardings`` places its leaves."""
    def fn(key):
        tree = build(shape, key, dtype, std)
        return tree if convert is None else convert(tree)

    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    return jax.jit(fn, **kw)(seed_key(seed))

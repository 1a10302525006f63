"""Plain AdamW, the optimizer every training cell's reference steps with
whatever its block. Imports nothing of the program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 2, 3))
def adamw(params, grads, mu, nu, count, hyper: tuple):
    """One AdamW step (Loshchilov & Hutter; decay on every leaf, as the
    configuration's train options state). ``hyper`` is
    ``(lr, b1, b2, eps, weight_decay)``; ``count`` is the step just taken,
    from 1."""
    lr, b1, b2, eps, wd = hyper
    t = count.astype(jnp.float32)

    def leaf(p, g, m, n):
        m = b1 * m + (1 - b1) * g
        n = b2 * n + (1 - b2) * g * g
        step = (m / (1 - b1**t)) / (jnp.sqrt(n / (1 - b2**t)) + eps)
        return p - lr * (step + wd * p), m, n

    out = jax.tree_util.tree_map(leaf, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda _, o: o[i], params, out
    )
    return pick(0), pick(1), pick(2)

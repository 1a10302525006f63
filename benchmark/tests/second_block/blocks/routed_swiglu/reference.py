"""Block ``routed_swiglu``, plain reference (a fixture of
``tests/test_add_files.py``, no configuration of ``BENCHMARK.json``): the
``gqa_swiglu`` block with its feed-forward replaced by routed experts.

``x + Attn(RMSNorm(x))`` as in ``gqa_swiglu``; then, for ``y = RMSNorm(x)``
of one token, ``p = softmax(y W_r)`` over the ``E`` experts, the ``k``
experts of largest ``p``, their ``p`` divided by the sum of the ``k``, and
``x + sum_i p_i SwiGLU_i(y)`` over those ``k``. No token is dropped, no
shared expert, no auxiliary loss. Every expert is computed for every token
and weighted by nought where it was not chosen: plain, and fit for a toy.

Parameter layout: ``gqa_swiglu``'s without ``w_gate`` / ``w_up`` /
``w_down``, and under ``layers`` ``router (L, d, E)``, ``experts_gate``,
``experts_up (L, E, d, ff)``, ``experts_down (L, E, ff, d)``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmark.blocks.gqa_swiglu import reference as dense

MODES = ("train",)


@dataclasses.dataclass(frozen=True)
class Shape(dense.Shape):
    num_experts: int
    num_experts_per_tok: int


def routed_ffn(y, lp, shape: Shape, lin):
    """``sum_i p_i SwiGLU_i(y)`` over each token's ``k`` experts; y (S, d)."""
    p = jax.nn.softmax(jnp.matmul(y, lp["router"], precision=dense.HIGHEST), -1)
    top, which = jax.lax.top_k(p, shape.num_experts_per_tok)
    top = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.sum(
        jax.nn.one_hot(which, shape.num_experts) * top[..., None], 1)  # (S, E)
    each = jax.vmap(
        lambda wg, wu, wd: lin(jax.nn.silu(lin(y, wg)) * lin(y, wu), wd)
    )(lp["experts_gate"], lp["experts_up"], lp["experts_down"])  # (E, S, d)
    return jnp.einsum("se,esd->sd", weight, each, precision=dense.HIGHEST)


def _block(x, lp, shape: Shape, precision: str):
    lin = functools.partial(dense.linear, precision=precision, weight_bits=8)
    x = dense.attention_sublayer(x, lp, shape, lin)
    y = dense.rms_norm(x, lp["mlp_norm"], shape.rms_norm_eps)
    return x + routed_ffn(y, lp, shape, lin)


def sequence_loss(params, tokens, targets, shape: Shape, precision="float32"):
    """Mean next-token cross entropy of one sequence."""
    x = params["embed"][tokens]
    block = jax.checkpoint(lambda x, lp: (_block(x, lp, shape, precision), None))
    x, _ = jax.lax.scan(block, x, params["layers"])
    x = dense.rms_norm(x, params["final_norm"], shape.rms_norm_eps)
    lg = dense.linear(x, params["head"], precision, 8)
    lse = jax.nn.logsumexp(lg, -1)
    return jnp.mean(lse - jnp.take_along_axis(lg, targets[:, None], 1)[:, 0])


def grad_fn(shape: Shape, precision="float32", placement=None):
    return dense.grad_fn(shape, precision, placement, loss=sequence_loss)


def leaf_shapes(shape: Shape) -> dict:
    spec = dense.leaf_shapes(shape)
    for name in ("w_gate", "w_up", "w_down"):
        del spec["layers"][name]
    d, ff, L = shape.hidden_size, shape.intermediate_size, shape.num_hidden_layers
    e = shape.num_experts
    spec["layers"].update(
        router=((L, d, e), "matrix"),
        experts_gate=((L, e, d, ff), "matrix"),
        experts_up=((L, e, d, ff), "matrix"),
        experts_down=((L, e, ff, d), "matrix"),
    )
    return spec


def _attention_params(shape: Shape) -> int:
    d = shape.hidden_size
    q = shape.num_attention_heads * shape.head_dim
    kv = shape.num_key_value_heads * shape.head_dim
    return d * (q + 2 * kv) + q * d


def total_params(shape: Shape) -> int:
    d, L = shape.hidden_size, shape.num_hidden_layers
    layer = (_attention_params(shape) + d * shape.num_experts
             + shape.num_experts * 3 * d * shape.intermediate_size)
    return L * layer + 2 * d * shape.vocab_size + d * (2 * L + 1)


def train_flops_per_token(shape: Shape, seq_len: int) -> float:
    """Forward and backward of one token: the matrices it passes through
    (attention, the router, ``k`` of the ``E`` experts, the head) and the
    attention scores, by ``gqa_swiglu``'s convention."""
    d, L = shape.hidden_size, shape.num_hidden_layers
    active = (_attention_params(shape) + d * shape.num_experts
              + shape.num_experts_per_tok * 3 * d * shape.intermediate_size)
    attn = 12 * L * shape.num_attention_heads * shape.head_dim * seq_len
    return 6.0 * (L * active + d * shape.vocab_size) + attn

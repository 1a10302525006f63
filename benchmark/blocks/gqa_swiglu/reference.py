"""Block ``gqa_swiglu``, plain reference: a pre-RMSNorm, rotary,
grouped-query, SwiGLU decoder with an untied head, its loss and its
gradients in float32 ``jax.numpy``, the tree of its leaves and its analytic
operation counts.

Written from the published description of the block (InternLM2 and Mistral
share it: ``x + Attn(RMSNorm(x))``, ``x + SwiGLU(RMSNorm(x))``, rotary
embedding in the half-split ``rotate_half`` convention of both models'
public code, K/V heads shared by groups of query heads, no bias, a final
RMSNorm and an untied output head). It imports nothing of the program and
takes nothing the program has made: ``benchmark/lib/weights.py`` makes the
weights from :func:`leaf_shapes`, in this file's own layout.

Every matrix product runs at ``Precision.HIGHEST`` (on a TPU a float32
product is otherwise one bfloat16 pass). One sequence at a time, layers
under ``lax.scan`` with the block rematerialized, attention one K/V head at
a time: it fits beside 7 GB of int8 weights.

The controls of ``correct`` are this same code one precision lower:
``precision="float8"`` (e4m3) or ``"int8"`` (dynamic absmax) rounds both
operands of every linear layer, straight-through gradient, where the
configuration states bfloat16 compute, and ``weight_bits=4`` rounds int8 weights to int4 where
it states int8 weights.

Parameter layout (``L`` layers stacked on the leading axis)::

    embed (V, d)   final_norm (d,)   head (d, V)
    layers: attn_norm (L, d)  wq (L, d, H*hd)  wk, wv (L, d, KV*hd)
            wo (L, H*hd, d)   mlp_norm (L, d)
            w_gate, w_up (L, d, ff)   w_down (L, ff, d)

A serving weight is ``{"q": int8 (..., K, N), "scale": f32 (..., 1, N)}``
in place of the float array, standing for ``q * scale``.

Operation counts are what the mathematics needs, whatever computes it.
Recomputed operations (rematerialization, a flash backward's second pass
over the scores) are not counted. PaLM's convention (appendix B): 2
operations a multiply-add, 6 N a trained token for N matrix parameters, and
12 L H hd S for the attention scores and their weighted sums over a full
S x S square; serving counts the causal triangle it really needs.

Another block's reference may import this file's pieces
(``from benchmark.blocks.gqa_swiglu import reference``): ``linear``,
``rms_norm``, ``rope``, ``attention_sublayer`` and ``grad_fn(..., loss=)``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("train", "serve")  # the cells this block can stand behind


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the equations need, under the published key names."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    rope_theta: float
    rms_norm_eps: float

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        return cls(**{f.name: config[f.name] for f in dataclasses.fields(cls)})


def _fake_int8(x, axis):
    """Round to 255 levels of the absmax along ``axis``; the gradient
    passes straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127
    return x + jax.lax.stop_gradient(jnp.round(x / s) * s - x)


def _fake_fp8(x):
    """Round to float8 (e4m3); the gradient passes straight through."""
    low = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    return x + jax.lax.stop_gradient(low - x)


def _weight(w, weight_bits: int):
    """A float array as it is; ``{"q", "scale"}`` as ``q * scale``, with
    the int8 values rounded to ``weight_bits`` first where that is 4."""
    if not isinstance(w, dict):
        return w
    q = w["q"].astype(jnp.float32)
    if weight_bits == 4:
        q = jnp.round(q * (7 / 127)) * (127 / 7)
    return q * w["scale"]


def linear(x, w, precision: str, weight_bits: int):
    w = _weight(w, weight_bits)
    if precision == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif precision == "float8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """Rotary embedding of ``x`` (S, heads, hd) at positions 0..S-1: pairs
    are (i, i + hd/2), the ``rotate_half`` convention."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal softmax attention, one K/V head with its group of query
    heads at a time. q (S, KV, G, hd); k, v (S, KV, hd)."""
    s, _, _, hd = q.shape
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))

    def one(args):
        qh, kh, vh = args  # (S, G, hd), (S, hd), (S, hd)
        scores = jnp.einsum("sgd,td->gst", qh, kh, precision=HIGHEST)
        scores = jnp.where(causal, scores / jnp.sqrt(jnp.float32(hd)), -1e30)
        return jnp.einsum(
            "gst,td->sgd", jax.nn.softmax(scores, -1), vh, precision=HIGHEST
        )

    out = jax.lax.map(
        one, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2))
    )  # (KV, S, G, hd)
    return out.transpose(1, 0, 2, 3).reshape(s, -1)


def attention_sublayer(x, lp, shape, lin):
    """``x + Attn(RMSNorm(x))`` of one sequence (S, d); ``lin`` is
    :func:`linear` with its controls bound."""
    s = x.shape[0]
    h, kv, hd = (
        shape.num_attention_heads, shape.num_key_value_heads, shape.head_dim
    )
    y = rms_norm(x, lp["attn_norm"], shape.rms_norm_eps)
    q = rope(lin(y, lp["wq"]).reshape(s, h, hd), shape.rope_theta)
    k = rope(lin(y, lp["wk"]).reshape(s, kv, hd), shape.rope_theta)
    v = lin(y, lp["wv"]).reshape(s, kv, hd)
    return x + lin(_attention(q.reshape(s, kv, h // kv, hd), k, v), lp["wo"])


def _block(x, lp, shape: Shape, precision: str, weight_bits: int):
    lin = functools.partial(
        linear, precision=precision, weight_bits=weight_bits
    )
    x = attention_sublayer(x, lp, shape, lin)
    y = rms_norm(x, lp["mlp_norm"], shape.rms_norm_eps)
    gated = jax.nn.silu(lin(y, lp["w_gate"])) * lin(y, lp["w_up"])
    return x + lin(gated, lp["w_down"])


def hidden(params, tokens, shape: Shape, precision="float32", weight_bits=8):
    """Final-norm hidden states (S, d) of one sequence ``tokens`` (S,)."""
    x = params["embed"][tokens]
    block = jax.checkpoint(
        lambda x, lp: (_block(x, lp, shape, precision, weight_bits), None)
    )
    x, _ = jax.lax.scan(block, x, params["layers"])
    return rms_norm(x, params["final_norm"], shape.rms_norm_eps)


def logits(
    params, tokens, shape: Shape, positions=None, precision="float32",
    weight_bits=8,
):
    """Logits (P, V) of one sequence at ``positions`` (all when None)."""
    x = hidden(params, tokens, shape, precision, weight_bits)
    if positions is not None:
        x = x[positions]
    return linear(x, params["head"], precision, weight_bits)


def sequence_loss(params, tokens, targets, shape: Shape, precision="float32"):
    """Mean next-token cross entropy of one sequence."""
    lg = logits(params, tokens, shape, precision=precision)
    lse = jax.nn.logsumexp(lg, -1)
    return jnp.mean(lse - jnp.take_along_axis(lg, targets[:, None], 1)[:, 0])


def grad_fn(shape: Shape, precision="float32", placement=None,
            loss=sequence_loss):
    """``fn(params, tokens, targets, rows=None)`` -> (mean loss over the
    batch rows, its gradients), a row at a time. ``rows`` limits the mean
    to those rows (the half-batch fault of the tests). ``placement`` is a
    tree of shardings for the gradients, where the parameters are spread
    over several chips because one cannot hold them: where a leaf lies,
    not what is computed. ``loss`` is the block's own
    ``(params, tokens, targets, shape, precision)`` of one sequence."""
    kw = {} if placement is None else {"out_shardings": (None, placement)}
    step = jax.jit(
        jax.value_and_grad(
            lambda p, x, y: loss(p, x, y, shape, precision)), **kw
    )
    add = jax.jit(
        lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=0
    )
    scale = jax.jit(
        lambda g, n: jax.tree_util.tree_map(lambda x: x / n, g), donate_argnums=0
    )

    def fn(params, tokens, targets, rows=None):
        rows = range(tokens.shape[0]) if rows is None else rows
        total, grads = 0.0, None
        for r in rows:
            loss_r, g = step(params, tokens[r], targets[r])
            total += float(loss_r)
            grads = g if grads is None else add(grads, g)
        n = len(rows)
        return total / n, scale(grads, jnp.float32(n))

    return fn


def leaf_shapes(shape: Shape) -> dict:
    """name -> (dims, kind) with kind ``matrix``, ``norm`` or ``embed``."""
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


def matmul_params(shape: Shape) -> dict:
    """Matrix parameters that multiply a token's activations: a layer's
    projections and feed-forward, and the output head. The embedding is a
    lookup and does no multiplication."""
    d, ff = shape.hidden_size, shape.intermediate_size
    q = shape.num_attention_heads * shape.head_dim
    kv = shape.num_key_value_heads * shape.head_dim
    layer = d * (q + 2 * kv) + q * d + 3 * d * ff
    return {
        "layer": layer,
        "layers": layer * shape.num_hidden_layers,
        "head": d * shape.vocab_size,
        "embedding": d * shape.vocab_size,
        "norms": d * (2 * shape.num_hidden_layers + 1),
    }


def total_params(shape: Shape) -> int:
    p = matmul_params(shape)
    return p["layers"] + p["head"] + p["embedding"] + p["norms"]


def train_flops_per_token(shape: Shape, seq_len: int) -> float:
    """Forward and backward of one token in a sequence of ``seq_len``."""
    p = matmul_params(shape)
    attn = (12 * shape.num_hidden_layers * shape.num_attention_heads
            * shape.head_dim * seq_len)
    return 6.0 * (p["layers"] + p["head"]) + attn


def serve_flops(shape: Shape, prompt_len: int, new_tokens: int) -> float:
    """One request: its prompt and all but the last generated token pass
    through the layers, each attending to what precedes it; the head is
    applied once for each generated token."""
    p = matmul_params(shape)
    through = prompt_len + new_tokens - 1
    attn = (4 * shape.num_hidden_layers * shape.num_attention_heads
            * shape.head_dim * through * (through + 1) / 2)
    return 2.0 * p["layers"] * through + 2.0 * p["head"] * new_tokens + attn

"""Block ``falcon_h1``, plain reference: a decoder whose every layer runs a
Mamba-2 mixer and grouped-query attention **in parallel on one normed
input** and sums them, then a SwiGLU MLP, with the published scalar
multipliers of its maximal-update parametrization (Falcon-H1,
``model_type`` ``falcon_h1``); float32 ``jax.numpy``, every product at
``Precision.HIGHEST``, no cache, no kernels, no chunks, one sequence. With
``d`` the hidden size, ``I = mamba_d_ssm = H * P`` (``H = mamba_n_heads``,
``P = mamba_d_head``), ``G = mamba_n_groups``, ``N = mamba_d_state``, ``K =
mamba_d_conv``, ``F = intermediate_size``::

    x_0 = embedding_multiplier * E[token]
    layer:  u = RMSNorm(x; w_input)
     Mamba-2:  p = W_in (ssm_in_multiplier * u)                      in R^(2I + 2GN + H)
               p = p * m,  m = [s0 x I | s1 x I | s2 x GN | s3 x GN | s4 x H],  s = ssm_multipliers
               [z | xBC | dt] = split(p, [I, I + 2GN, H])
               xBC_t = silu(sum_{k<K} w_conv[k] * xBC_{t-K+1+k} + b_conv)     causal, depthwise, zeros before the start
               [x | B | C] = split(xBC, [I, GN, GN]);  x -> (H, P), B, C -> (G, N);  g(h) = h // (H / G)
               delta_t = softplus(dt_t + dt_bias)   in R^H;   A = -exp(A_log)   in R^H
               h_t[h] = exp(delta_t[h] A[h]) h_{t-1}[h] + delta_t[h] * B_t[g(h)] (outer) x_t[h]     (N x P), h_0 = 0
               y_t[h] = C_t[g(h)] h_t[h] + D[h] x_t[h]
               y = w_norm * GroupRMS_G(y * silu(z))     gate first, mean square over each group of I / G channels
               m_out = ssm_out_multiplier * W_out y
     attention: a = attention_in_multiplier * u;  q = W_q a;  k = key_multiplier * W_k a;  v = W_v a
               rotary (rope_theta, half-split) on q and k;  causal softmax(q k^T / sqrt(head_dim)) v
               a_out = attention_out_multiplier * W_o (.)
     x' = x + m_out + a_out
     x'' = x' + down_multiplier * W_down(silu(gate_multiplier * W_gate v) * W_up v),  v = RMSNorm(x'; w_pre_ff)
    logits = lm_head_multiplier * W_head RMSNorm(x_L; w_final)

``head_dim`` is a published size of its own (20 heads of 128 under a hidden
size of 5,120). The state is written here ``(N, P)`` a head, the transpose
of the paper's, as the program stores it; the sums are the same.

Leaves are laid out **as the program stores them**: 2-D ``(in, out)``
matrices with the heads flattened, the layers stacked on a leading axis
(``layers``), the convolution ``(K, I + 2GN)`` with the channels innermost,
``dt_bias``, ``A_log`` and ``D`` a head; ``W_in``'s columns in two leaves,
``in_proj`` (``[z | x | B | C]``, 2I + 2GN) and ``dt_proj`` (H): 9,248
columns are no whole number of 128-lane tiles, 9,216 are. The embedding is a ``matrix`` leaf:
served, it is int8 values with one float32 scale a hidden column like every
other matrix (a float32 embedding of 261,120 x 5,120 is 5.3 GB, a third of
the chip), row ``v`` of ``E`` those values dequantized; the program's half
makes its lookup table from them once, in the compute type.

**How the seed's numbers become parameters.** ``benchmark/lib/weights.py``
draws a matrix (int8 values and a scale a column when served), ones for a
``norm`` and ``N(0, initializer_range)`` for an ``embed`` leaf.
:func:`drawn` turns some ``embed`` draws ``g`` into the parameter the
equations use, through ``unit = g / rms(g)`` so that none depends on
``initializer_range``: the convolution's taps ``0.5 unit``; ``A_log[h] =
log(1 + 15 h / (H - 1)) + 0.02 unit`` (decays 1..16 spread over the heads,
Mamba-2's start); ``dt_bias[h] = softplus^-1(step_h) + 0.5 unit`` with
``step_h = 1e-3 * 100^(perm(h) / (H - 1))`` (steps log-spread over
1e-3..0.1, ``perm(h) = 13 h mod H`` so that a fast decay does not always
meet a long step); ``D`` is a ``norm`` leaf (ones). Both sides of the
comparison read the tree through it.

A serving weight is ``{"q": int8, "scale": float32 (..., 1, N)}`` as in
``gqa_swiglu``; ``weight_bits=4`` (the control) rounds it to int4, the
embedding's rows with it. The head is applied in column blocks
(``_HEAD_BLOCK``): dequantized whole it is 5.3 GB of float32.

Operation counts are what the equations need, 2 a multiply-add. A token
passes a layer's matrices, ``6 H P N`` operations of state (decay, update,
read-out: a multiply-add each) and ``2 K (I + 2GN)`` of convolution; a query
meets a key with ``4 head_dim`` operations a head (a score and a weighted
value); the head is applied once a generated token.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmark.blocks.gqa_swiglu.reference import (
    HIGHEST,
    linear,
    rms_norm,
    rope,
)

MODES = ("serve",)
_HEAD_BLOCK = 32768  # the most columns of the head dequantized at a time


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes and scalars the equations need, under the published key
    names."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float
    rms_norm_eps: float
    mamba_d_ssm: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_n_groups: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_chunk_size: int
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: tuple
    mlp_multipliers: tuple
    # not a size: the type the served program keeps its lookup table in
    # (``serve.compute_dtype``), which the program's half needs to make it
    compute_dtype: str = "float32"

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        sizes = {
            f.name: (tuple(config[f.name])
                     if isinstance(config[f.name], list) else config[f.name])
            for f in dataclasses.fields(cls) if f.name != "compute_dtype"
        }
        # 100000000000 as JSON has it is no 32-bit integer
        sizes["rope_theta"] = float(sizes["rope_theta"])
        shape = cls(**sizes, compute_dtype=config["serve"]["compute_dtype"])
        if shape.mamba_d_ssm != shape.mamba_n_heads * shape.mamba_d_head:
            raise ValueError(
                f"mamba_d_ssm {shape.mamba_d_ssm} is not mamba_n_heads x "
                f"mamba_d_head ({shape.mamba_n_heads} x {shape.mamba_d_head})")
        return shape

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_width(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads


# -- the seed's draws as parameters -----------------------------------------

def _draw(name: str, g):
    """``g`` is the leaf's ``N(0, initializer_range)`` draw; ``unit`` the
    same numbers at deviation 1, so that no parameter here depends on
    ``initializer_range``."""
    if name not in ("conv_weight", "a_log", "dt_bias"):
        return g
    unit = g / jnp.sqrt(jnp.mean(g * g))
    if name == "conv_weight":
        return 0.5 * unit
    h = g.shape[-1]
    if name == "a_log":
        at = jnp.arange(h, dtype=jnp.float32)
        return jnp.log1p(15.0 * at / max(h - 1, 1)) + 0.02 * unit
    step = 1e-3 * 100.0 ** (((13 * jnp.arange(h)) % h) / max(h - 1, 1))
    return jnp.log(jnp.expm1(step)) + 0.5 * unit


def drawn(tree: dict) -> dict:
    """The tree with each leaf as the parameter the equations use."""
    return {
        k: drawn(v) if isinstance(v, dict) and "q" not in v else _draw(k, v)
        for k, v in tree.items()
    }


# -- the equations ------------------------------------------------------------

def embed_rows(embed, tokens, weight_bits: int):
    """Rows ``tokens`` of the embedding."""
    if not isinstance(embed, dict):
        return embed[tokens]
    q = embed["q"][tokens].astype(jnp.float32)
    if weight_bits == 4:
        q = jnp.round(q * (7 / 127)) * (127 / 7)
    return q * embed["scale"]


def gate_norm(y, z, weight, shape: Shape):
    """``w * GroupRMS_G(y * silu(z))``: the gate first
    (``mamba_norm_before_gate`` false)."""
    gated = y * jax.nn.silu(z)
    s = gated.shape[0]
    grouped = gated.reshape(s, shape.mamba_n_groups, -1)
    normed = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + shape.rms_norm_eps)
    return normed.reshape(s, -1) * weight


def mamba2(u, p, shape: Shape, lin):
    """``m_out`` (S, d) of one sequence ``u`` (S, d)."""
    inner, h, hp = shape.mamba_d_ssm, shape.mamba_n_heads, shape.mamba_d_head
    g, n, taps = shape.mamba_n_groups, shape.mamba_d_state, shape.mamba_d_conv
    s = u.shape[0]
    # W_in's columns lie in two leaves: [z | x | B | C] and dt
    proj = jnp.concatenate([
        lin(shape.ssm_in_multiplier * u, p["in_proj"]),
        lin(shape.ssm_in_multiplier * u, p["dt_proj"])], -1)
    mult = jnp.concatenate([
        jnp.full((width,), m, jnp.float32) for width, m in zip(
            (inner, inner, g * n, g * n, h), shape.ssm_multipliers)
    ])
    proj = proj * mult
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + shape.conv_dim],
                  proj[:, inner + shape.conv_dim:])
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(
        sum(padded[k:k + s] * p["conv_weight"][k] for k in range(taps))
        + p["conv_bias"])
    x = xbc[:, :inner].reshape(s, h, hp)
    bm = xbc[:, inner:inner + g * n].reshape(s, g, n)
    cm = xbc[:, inner + g * n:].reshape(s, g, n)
    delta = jax.nn.softplus(dt + p["dt_bias"])  # (S, H)
    a = -jnp.exp(p["a_log"])  # (H,)
    of_head = jnp.arange(h) // (h // g)

    def step(state, inp):  # state (H, N, P)
        x_t, d_t, b_t, c_t = inp
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + b_t[of_head][:, :, None] * (d_t[:, None] * x_t)[:, None, :])
        return state, jnp.sum(state * c_t[of_head][:, :, None], 1)

    _, y = jax.lax.scan(
        step, jnp.zeros((h, n, hp), jnp.float32), (x, delta, bm, cm))
    y = (y + p["d_skip"][:, None] * x).reshape(s, inner)
    y = gate_norm(y, z, p["ssm_norm"], shape)
    return shape.ssm_out_multiplier * lin(y, p["out_proj"])


def causal_attention(q, k, v):
    """Causal softmax attention, one KV head with its group of query heads
    at a time. q (S, KV, R, hd); k, v (S, KV, hd). Returns (S, KV * R * hd)."""
    s, _, _, hd = q.shape
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))

    def one(args):
        qh, kh, vh = args  # (S, R, hd), (S, hd), (S, hd)
        scores = jnp.einsum("srd,td->rst", qh, kh, precision=HIGHEST)
        scores = jnp.where(causal, scores / jnp.sqrt(jnp.float32(hd)), -1e30)
        return jnp.einsum(
            "rst,td->srd", jax.nn.softmax(scores, -1), vh, precision=HIGHEST)

    out = jax.lax.map(
        one, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(s, -1)


def attention(u, p, shape: Shape, lin):
    """``a_out`` (S, d) of one sequence ``u`` (S, d)."""
    s = u.shape[0]
    h, kv, hd = shape.num_attention_heads, shape.num_key_value_heads, shape.head_dim
    a = shape.attention_in_multiplier * u
    q = rope(lin(a, p["wq"]).reshape(s, h, hd), shape.rope_theta)
    k = rope((shape.key_multiplier * lin(a, p["wk"])).reshape(s, kv, hd),
             shape.rope_theta)
    v = lin(a, p["wv"]).reshape(s, kv, hd)
    out = causal_attention(q.reshape(s, kv, h // kv, hd), k, v)
    return shape.attention_out_multiplier * lin(out, p["wo"])


def block(x, p, shape: Shape, lin):
    u = rms_norm(x, p["input_norm"], shape.rms_norm_eps)
    x = x + mamba2(u, p, shape, lin) + attention(u, p, shape, lin)
    v = rms_norm(x, p["pre_ff_norm"], shape.rms_norm_eps)
    gate_mult, down_mult = shape.mlp_multipliers
    gated = jax.nn.silu(gate_mult * lin(v, p["w_gate"])) * lin(v, p["w_up"])
    return x + down_mult * lin(gated, p["w_down"])


def hidden(params, tokens, shape: Shape, precision="float32", weight_bits=8):
    """Final-norm hidden states (S, d) of one sequence ``tokens`` (S,)."""
    params = drawn(params)
    lin = functools.partial(linear, precision=precision, weight_bits=weight_bits)
    x = shape.embedding_multiplier * embed_rows(
        params["embed"], tokens, weight_bits)
    x, _ = jax.lax.scan(
        lambda x, lp: (block(x, lp, shape, lin), None), x, params["layers"])
    return rms_norm(x, params["final_norm"], shape.rms_norm_eps)


def logits(params, tokens, shape: Shape, positions=None, precision="float32",
           weight_bits=8):
    """Logits (P, V) of one sequence at ``positions`` (all when None)."""
    x = hidden(params, tokens, shape, precision, weight_bits)
    if positions is not None:
        x = x[positions]
    head, vocab = params["head"], shape.vocab_size
    # the fewest equal column blocks of at most _HEAD_BLOCK (8 of 32,640)
    blocks = next((n for n in range(1, 65) if vocab % n == 0
                   and vocab // n <= _HEAD_BLOCK), 1)
    if blocks == 1:
        return shape.lm_head_multiplier * linear(x, head, precision, weight_bits)
    width = vocab // blocks

    def columns(j):
        part = jax.tree_util.tree_map(
            lambda t: jax.lax.dynamic_slice_in_dim(t, j * width, width, axis=1),
            head)
        return linear(x, part, precision, weight_bits)

    out = jax.lax.map(columns, jnp.arange(blocks))  # (blocks, P, width)
    return shape.lm_head_multiplier * jnp.moveaxis(out, 0, 1).reshape(
        x.shape[0], vocab)


# -- leaves and counts --------------------------------------------------------

def leaf_shapes(shape: Shape) -> dict:
    """name -> (dims, kind), nested as the equations read it."""
    d, ff, L = shape.hidden_size, shape.intermediate_size, shape.num_hidden_layers
    q = shape.num_attention_heads * shape.head_dim
    kv = shape.num_key_value_heads * shape.head_dim
    inner, h = shape.mamba_d_ssm, shape.mamba_n_heads
    layers = {
        "input_norm": ((L, d), "norm"),
        "wq": ((L, d, q), "matrix"),
        "wk": ((L, d, kv), "matrix"),
        "wv": ((L, d, kv), "matrix"),
        "wo": ((L, q, d), "matrix"),
        "in_proj": ((L, d, inner + shape.conv_dim), "matrix"),
        "dt_proj": ((L, d, h), "matrix"),
        "conv_weight": ((L, shape.mamba_d_conv, shape.conv_dim), "embed"),
        "conv_bias": ((L, shape.conv_dim), "embed"),
        "dt_bias": ((L, h), "embed"),
        "a_log": ((L, h), "embed"),
        "d_skip": ((L, h), "norm"),
        "ssm_norm": ((L, inner), "norm"),
        "out_proj": ((L, inner, d), "matrix"),
        "pre_ff_norm": ((L, d), "norm"),
        "w_gate": ((L, d, ff), "matrix"),
        "w_up": ((L, d, ff), "matrix"),
        "w_down": ((L, ff, d), "matrix"),
    }
    return {
        "embed": ((shape.vocab_size, d), "matrix"),
        "layers": layers,
        "final_norm": ((d,), "norm"),
        "head": ((d, shape.vocab_size), "matrix"),
    }


def matmul_params(shape: Shape) -> dict:
    """Matrix parameters that multiply a token's activations, a layer by
    branch, and the head. The embedding is a lookup."""
    d = shape.hidden_size
    q = shape.num_attention_heads * shape.head_dim
    kv = shape.num_key_value_heads * shape.head_dim
    return {
        "attention": d * (q + 2 * kv) + q * d,
        "mamba": d * shape.in_proj_width + shape.mamba_d_ssm * d,
        "mlp": 3 * d * shape.intermediate_size,
        "head": d * shape.vocab_size,
        "embedding": d * shape.vocab_size,
    }


def total_params(shape: Shape) -> int:
    p = matmul_params(shape)
    small = (shape.mamba_d_conv * shape.conv_dim + shape.conv_dim  # taps, bias
             + 3 * shape.mamba_n_heads + shape.mamba_d_ssm  # dt_bias, A_log, D; w_norm
             + 2 * shape.hidden_size)  # the two norms
    layer = p["attention"] + p["mamba"] + p["mlp"] + small
    return (layer * shape.num_hidden_layers + p["head"] + p["embedding"]
            + shape.hidden_size)


def serve_flops(shape: Shape, prompt_len: int, new_tokens: int) -> float:
    """One request: its prompt and all but the last generated token pass
    through the layers, each attending to what precedes it and stepping the
    state once; the head is applied once for each generated token."""
    p = matmul_params(shape)
    through = prompt_len + new_tokens - 1
    state = (6.0 * shape.mamba_n_heads * shape.mamba_d_head * shape.mamba_d_state
             + 2.0 * shape.mamba_d_conv * shape.conv_dim)
    a_token = 2.0 * (p["attention"] + p["mamba"] + p["mlp"]) + state
    meet = (4.0 * shape.num_attention_heads * shape.head_dim
            * through * (through + 1) / 2)
    return (shape.num_hidden_layers * (a_token * through + meet)
            + 2.0 * p["head"] * new_tokens)

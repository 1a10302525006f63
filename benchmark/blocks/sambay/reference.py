"""Block ``sambay``, plain reference: a decoder of Mamba-1 layers, sliding-
window and full differential attention, Gated Memory Units and cross-
attention on ONE layer's K and V (SambaY, arXiv:2507.06607), as
Phi-4-mini-flash-reasoning's ``config.json`` (``model_type`` ``phi4flash``)
lays it out; float32 ``jax.numpy``, every product at ``Precision.HIGHEST``,
no cache, no kernels, one sequence. With ``d`` the hidden size, ``LN``
LayerNorm (mean subtracted, weight and bias, ``layer_norm_eps``), L layers
and ``half = L / 2``::

    x = E[t]                                   E the embedding = the head's transpose (tied)
    h = x + Mixer_l(LN1(x));  out = h + W_d(up * silu(gate)),  [gate | up] = W_gu LN2(h)
    logits = LN_f(x) E^T                       no positional encoding anywhere

    Mixer_l:  l even, l <= half   Mamba        l odd, l < half    window attention
              l == half + 1       full causal attention
              l even, l >= half+2 GMU          l odd, l >= half+3 cross-attention

**Mamba-1** (E = expand * d, N = d_state, R = dt_rank, ``d_conv`` taps):
``[u | z] = W_in x``; ``u'_t = silu(b_c + sum_k w_k * u_{t - d_conv + 1 + k})``
(causal, depthwise); ``[dr | B | C] = W_x u'``;
``delta = softplus(W_dt dr + b_dt)``; ``A = -exp(A_log)``;
``s_t = exp(delta_t A) * s_{t-1} + (delta_t u'_t) B_t^T`` (s_{-1} = 0);
``y_t = s_t C_t + D * u'_t``; result ``W_out(y * silu(z))``. Layer ``half``
also hands on ``m = y``, before the gate.

**GMU**: ``W_out(silu(W_in x) * m)``, ``m`` layer ``half``'s, same token.

**Differential attention** (arXiv:2410.05258 as ``phi4flash`` applies it),
every attention layer. Query heads ``2i``, ``2i + 1`` are pair ``i``; KV
heads ``2j``, ``2j + 1`` KV pair ``j``; pair ``i`` reads KV pair
``i // (pairs / KV pairs)``. ``P1 = softmax(q_2i k_2j^T / sqrt(hd))``,
``P2 = softmax(q_2i+1 k_2j+1^T / sqrt(hd))`` under the layer's mask,
``V = [v_2j | v_2j+1]``; ``a = P1 V - lambda P2 V``,
``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``a <- RMSNorm(a) * w * (1 -
lambda_init)`` over the pair's ``2 hd`` numbers (eps ``layer_norm_eps``);
the pairs' results side by side through ``W_o`` with bias. ``W_qkv`` has a
bias. The window layers' mask lets position ``t`` see ``t - window + 1 ..
t``; layer ``half + 1``'s everything up to ``t``. **Cross-attention**:
``q = W_q x + b``, K and V are layer ``half + 1``'s, causal, differential
with the layer's own lambda vectors and norm weight, own ``W_o``.

Leaves are laid out **as the program stores them**: 2-D ``(in, out)``
matrices with the heads flattened, ``A_log`` and the state ``(N, E)``, the
convolution ``(taps, E)``; the two scanned stacks ``layers_a`` (a Mamba and
a window block a period) and ``layers_b`` (a GMU and a cross block) with a
leading axis, the two layers between them (``mid_mamba``, ``mid_full``) on
their own; no embedding leaf: **the embedding is the head**, row ``v`` of E
column ``v`` of ``head`` (dequantized where it is int8), so the program's
half only renames and derives the embedding.

**How the seed's numbers become parameters.** ``benchmark/lib/weights.py``
draws a matrix (int8 values and a scale a column when served), ones for a
``norm`` and ``N(0, initializer_range)`` for an ``embed`` leaf.
:func:`drawn` turns some ``embed`` draws ``g`` into the parameter the
equations use, ``offset + gain * g``: the convolution's taps at a deviation
of 0.5, ``A_log = log(n + 1) + g`` (the S4D-real start), ``b_dt`` around
``softplus^-1(0.01)`` so that a state remembers some hundred positions, the
lambda vectors at 0.1. Both sides of the comparison read them through it.

A serving weight is ``{"q": int8, "scale": float32 (..., 1, N)}`` as in
``gqa_swiglu``; ``weight_bits=4`` (the control) rounds it to int4, the
embedding's rows with it.

Operation counts are what the equations need, 2 a multiply-add. A prompt's
positions pass layers ``0 .. half`` and layer ``half + 1``'s K and V; its
last position alone that layer's attention and every later layer
(``prefill's cross-decoder once a request``); a generated token passes
everything. A scan position is 6 E N + 2 taps E; a query meets a key with
12 hd operations a pair (two scores over hd, two values over 2 hd).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from benchmark.blocks.gqa_swiglu.reference import HIGHEST, linear

MODES = ("serve",)
LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the equations need, under the published key names; the
    Mamba sizes are the ``phi4flash`` class's defaults (``assumed``)."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    sliding_window: int
    mb_per_layer: int
    layer_norm_eps: float
    mamba_d_state: int
    mamba_d_conv: int
    mamba_expand: int
    mamba_dt_rank: int
    # not a size: the type the served program keeps its lookup table in
    # (``serve.compute_dtype``), which the program's half needs to make it
    compute_dtype: str = "float32"

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        sizes = {f.name: config[f.name] for f in dataclasses.fields(cls)
                 if f.name != "compute_dtype"}
        return cls(**sizes, compute_dtype=config["serve"]["compute_dtype"])

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def half(self) -> int:
        return self.num_hidden_layers // 2

    @property
    def periods_a(self) -> int:
        return self.half // 2

    @property
    def periods_b(self) -> int:
        return (self.num_hidden_layers - self.half - 2) // 2


def kind_of(shape: Shape, l: int) -> str:
    if l % shape.mb_per_layer == 0:
        return "mamba" if l <= shape.half else "gmu"
    if l < shape.half:
        return "window"
    return "full" if l == shape.half + 1 else "cross"


# -- the seed's draws as parameters -----------------------------------------

def _draw(name: str, g):
    if name == "conv_weight":
        return 25.0 * g
    if name == "dt_bias":
        return math.log(math.expm1(0.01)) + 25.0 * g
    if name == "a_log":
        n = g.shape[-2]
        return jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None] + g
    if name in LAMBDAS:
        return 5.0 * g
    return g


def drawn(tree: dict) -> dict:
    """The tree with each leaf as the parameter the equations use."""
    return {
        k: drawn(v) if isinstance(v, dict) and "q" not in v else _draw(k, v)
        for k, v in tree.items()
    }


# -- the equations ------------------------------------------------------------

def layer_norm(x, p, eps: float):
    c = x - jnp.mean(x, -1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + eps) * p["scale"] + p["bias"]


def embed_rows(head, tokens, weight_bits: int):
    """Rows ``tokens`` of the embedding: columns of the head."""
    if not isinstance(head, dict):
        return head[:, tokens].T
    q = head["q"][:, tokens].astype(jnp.float32)
    if weight_bits == 4:
        q = jnp.round(q * (7 / 127)) * (127 / 7)
    return (q * head["scale"][:, tokens]).T


def mamba(x, p, shape: Shape, lin):
    """(result (S, d), y (S, E)) of one sequence ``x`` (S, d)."""
    e, n, r, taps = shape.d_inner, shape.mamba_d_state, shape.mamba_dt_rank, shape.mamba_d_conv
    s = x.shape[0]
    uz = lin(x, p["in_proj"])
    u, z = uz[:, :e], uz[:, e:]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    conv = sum(padded[k:k + s] * p["conv_weight"][k] for k in range(taps))
    up = jax.nn.silu(conv + p["conv_bias"])
    dbc = lin(up, p["x_proj"])
    dr, bm, cm = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    delta = jax.nn.softplus(lin(dr, p["dt_proj"]) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])  # (N, E)

    def step(state, inp):
        u_t, d_t, b_t, c_t = inp
        state = jnp.exp(d_t[None, :] * a) * state + (d_t * u_t)[None, :] * b_t[:, None]
        return state, jnp.sum(state * c_t[:, None], 0)

    _, y = jax.lax.scan(step, jnp.zeros((n, e), jnp.float32), (up, delta, bm, cm))
    y = y + p["d_skip"] * up
    return lin(y * jax.nn.silu(z), p["out_proj"]), y


def differential(q, k, v, p, shape: Shape, l, window):
    """The pairs' combined, normed results (S, H * hd). q (S, H, hd); k, v
    (T, KV, hd), position ``t`` of q attending keys ``<= t`` (and, with
    ``window``, the ``window`` newest of them)."""
    s, h, hd = q.shape
    t, kv, _ = k.shape
    pairs, kv_pairs = h // 2, kv // 2
    rows, cols = jnp.arange(s)[:, None], jnp.arange(t)[None, :]
    mask = cols <= rows
    if window is not None:
        mask = mask & (cols > rows - window)
    init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, jnp.float32))
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init)

    def softmax_of(qh, kh):
        scores = jnp.matmul(qh, kh.T, precision=HIGHEST) / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(mask, scores, -1e30), -1)

    def one(i):
        j = i // (pairs // kv_pairs)
        vv = jnp.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], -1)  # (T, 2 hd)
        p1 = softmax_of(q[:, 2 * i], k[:, 2 * j])
        p2 = softmax_of(q[:, 2 * i + 1], k[:, 2 * j + 1])
        a = (jnp.matmul(p1, vv, precision=HIGHEST)
             - lam * jnp.matmul(p2, vv, precision=HIGHEST))
        a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + shape.layer_norm_eps)
        return a * p["subln"] * (1.0 - init)

    out = jax.lax.map(one, jnp.arange(pairs))  # (pairs, S, 2 hd)
    return out.transpose(1, 0, 2).reshape(s, h * hd)


def attention(x, p, shape: Shape, lin, l, window):
    """(result, k, v) of a layer with K and V of its own."""
    s = x.shape[0]
    h, kv, hd = shape.num_attention_heads, shape.num_key_value_heads, shape.head_dim
    qkv = lin(x, p["qkv_proj"]) + p["qkv_bias"]
    q = qkv[:, :h * hd].reshape(s, h, hd)
    k = qkv[:, h * hd:(h + kv) * hd].reshape(s, kv, hd)
    v = qkv[:, (h + kv) * hd:].reshape(s, kv, hd)
    a = differential(q, k, v, p, shape, l, window)
    return lin(a, p["o_proj"]) + p["o_bias"], k, v


def cross_attention(x, k, v, p, shape: Shape, lin, l):
    s = x.shape[0]
    q = (lin(x, p["q_proj"]) + p["q_bias"]).reshape(
        s, shape.num_attention_heads, shape.head_dim)
    return lin(differential(q, k, v, p, shape, l, None), p["o_proj"]) + p["o_bias"]


def block(x, p, shape: Shape, lin, mixer):
    """``h = x + mixer(LN1(x)); h + MLP(LN2(h))``; what else the mixer
    gave rides out beside it."""
    y, *extra = mixer(layer_norm(x, p["ln1"], shape.layer_norm_eps))
    h = x + y
    gu = lin(layer_norm(h, p["ln2"], shape.layer_norm_eps), p["gate_up"])
    ff = shape.intermediate_size
    out = h + lin(gu[:, ff:] * jax.nn.silu(gu[:, :ff]), p["down"])
    return (out, *extra)


def hidden(params, tokens, shape: Shape, precision="float32", weight_bits=8):
    """Final-norm hidden states (S, d) of one sequence ``tokens`` (S,)."""
    params = drawn(params)
    lin = functools.partial(linear, precision=precision, weight_bits=weight_bits)
    win = shape.sliding_window
    x = embed_rows(params["head"], tokens, weight_bits)

    def period_a(x, args):  # a Mamba block, then a window block
        pa, i = args
        x, _ = block(x, pa["mamba"], shape, lin,
                     lambda z: mamba(z, pa["mamba"], shape, lin))
        x, _, _ = block(x, pa["window"], shape, lin,
                        lambda z: attention(z, pa["window"], shape, lin, 2 * i + 1, win))
        return x, None

    x, _ = jax.lax.scan(
        period_a, x, (params["layers_a"], jnp.arange(shape.periods_a)))
    pm = params["mid_mamba"]
    x, m = block(x, pm, shape, lin, lambda z: mamba(z, pm, shape, lin))
    pf = params["mid_full"]
    x, k, v = block(x, pf, shape, lin,
                    lambda z: attention(z, pf, shape, lin, shape.half + 1, None))

    def period_b(x, args):  # a GMU block, then a cross-attention block
        pb, i = args
        g, c = pb["gmu"], pb["cross"]
        x, = block(x, g, shape, lin, lambda z: (
            lin(jax.nn.silu(lin(z, g["in_proj"])) * m, g["out_proj"]),))
        x, = block(x, c, shape, lin, lambda z: (
            cross_attention(z, k, v, c, shape, lin, shape.half + 3 + 2 * i),))
        return x, None

    x, _ = jax.lax.scan(
        period_b, x, (params["layers_b"], jnp.arange(shape.periods_b)))
    return layer_norm(x, params["final_norm"], shape.layer_norm_eps)


def logits(params, tokens, shape: Shape, positions=None, precision="float32",
           weight_bits=8):
    """Logits (P, V) of one sequence at ``positions`` (all when None)."""
    x = hidden(params, tokens, shape, precision, weight_bits)
    if positions is not None:
        x = x[positions]
    return linear(x, params["head"], precision, weight_bits)


# -- leaves and counts --------------------------------------------------------

def _block_leaves(shape: Shape, lead: tuple) -> dict:
    d, ff = shape.hidden_size, shape.intermediate_size
    return {
        "ln1": {"scale": (lead + (d,), "norm"), "bias": (lead + (d,), "embed")},
        "ln2": {"scale": (lead + (d,), "norm"), "bias": (lead + (d,), "embed")},
        "gate_up": (lead + (d, 2 * ff), "matrix"),
        "down": (lead + (ff, d), "matrix"),
    }


def _mamba_leaves(shape: Shape, lead: tuple) -> dict:
    d, e, n = shape.hidden_size, shape.d_inner, shape.mamba_d_state
    r, taps = shape.mamba_dt_rank, shape.mamba_d_conv
    return dict(
        _block_leaves(shape, lead),
        in_proj=(lead + (d, 2 * e), "matrix"),
        conv_weight=(lead + (taps, e), "embed"), conv_bias=(lead + (e,), "embed"),
        x_proj=(lead + (e, r + 2 * n), "matrix"),
        dt_proj=(lead + (r, e), "matrix"), dt_bias=(lead + (e,), "embed"),
        a_log=(lead + (n, e), "embed"), d_skip=(lead + (e,), "norm"),
        out_proj=(lead + (e, d), "matrix"),
    )


def _attention_leaves(shape: Shape, lead: tuple, own_kv: bool) -> dict:
    d, hd = shape.hidden_size, shape.head_dim
    h, kv = shape.num_attention_heads, shape.num_key_value_heads
    out = dict(
        _block_leaves(shape, lead),
        o_proj=(lead + (h * hd, d), "matrix"), o_bias=(lead + (d,), "embed"),
        subln=(lead + (2 * hd,), "norm"),
        **{name: (lead + (hd,), "embed") for name in LAMBDAS},
    )
    width = (h + 2 * kv) * hd if own_kv else h * hd
    name = "qkv" if own_kv else "q"
    out[name + "_proj"] = (lead + (d, width), "matrix")
    out[name + "_bias"] = (lead + (width,), "embed")
    return out


def _gmu_leaves(shape: Shape, lead: tuple) -> dict:
    d, e = shape.hidden_size, shape.d_inner
    return dict(_block_leaves(shape, lead),
                in_proj=(lead + (d, e), "matrix"), out_proj=(lead + (e, d), "matrix"))


def leaf_shapes(shape: Shape) -> dict:
    """name -> (dims, kind), nested as the equations read it."""
    d = shape.hidden_size
    a, b = (shape.periods_a,), (shape.periods_b,)
    return {
        "head": ((d, shape.vocab_size), "matrix"),
        "final_norm": {"scale": ((d,), "norm"), "bias": ((d,), "embed")},
        "layers_a": {"mamba": _mamba_leaves(shape, a),
                     "window": _attention_leaves(shape, a, True)},
        "mid_mamba": _mamba_leaves(shape, ()),
        "mid_full": _attention_leaves(shape, (), True),
        "layers_b": {"gmu": _gmu_leaves(shape, b),
                     "cross": _attention_leaves(shape, b, False)},
    }


def matmul_params(shape: Shape) -> dict:
    """Matrix parameters a layer, by kind."""
    d, e, n, r = shape.hidden_size, shape.d_inner, shape.mamba_d_state, shape.mamba_dt_rank
    h, kv, hd = shape.num_attention_heads, shape.num_key_value_heads, shape.head_dim
    return {
        "mlp": 3 * d * shape.intermediate_size,
        "mamba": d * 2 * e + e * (r + 2 * n) + r * e + e * d,
        "kv": d * 2 * kv * hd,  # the K and V columns of W_qkv
        "own": d * (h + 2 * kv) * hd + h * hd * d,
        "cross": 2 * d * h * hd,
        "gmu": 2 * d * e,
        "head": d * shape.vocab_size,
    }


def layer_counts(shape: Shape) -> dict:
    return {"mamba": shape.periods_a + 1, "own": shape.periods_a + 1,
            "gmu": shape.periods_b, "cross": shape.periods_b}


def total_params(shape: Shape) -> int:
    p, c = matmul_params(shape), layer_counts(shape)
    d, e, n, hd = shape.hidden_size, shape.d_inner, shape.mamba_d_state, shape.head_dim
    h, kv = shape.num_attention_heads, shape.num_key_value_heads
    small = {
        "mamba": shape.mamba_d_conv * e + e + e + n * e + e,
        "own": (h + 2 * kv) * hd + d + 6 * hd,
        "cross": h * hd + d + 6 * hd,
        "gmu": 0,
    }
    layers = sum(c[k] * (p[k] + p["mlp"] + small[k] + 4 * d) for k in c)
    return layers + p["head"] + 2 * d


def serve_flops(shape: Shape, prompt_len: int, new_tokens: int) -> float:
    """One request. The prompt's positions pass the Mamba and window layers,
    layer ``half``, and layer ``half + 1``'s K and V; its last position and
    every generated token but the last pass everything; the head is applied
    once a generated token."""
    p, c = matmul_params(shape), layer_counts(shape)
    e, n, hd = shape.d_inner, shape.mamba_d_state, shape.head_dim
    pairs, w = shape.num_attention_heads // 2, shape.sliding_window
    decoded = new_tokens - 1
    early = c["mamba"] * (p["mamba"] + p["mlp"]) + shape.periods_a * (p["own"] + p["mlp"])
    late = (p["own"] + p["mlp"]
            + c["gmu"] * (p["gmu"] + p["mlp"]) + c["cross"] * (p["cross"] + p["mlp"]))
    matrices = (2.0 * early * (prompt_len + decoded)
                + 2.0 * p["kv"] * (prompt_len - 1)  # K and V of the prompt but its last
                + 2.0 * late * (1 + decoded)
                + 2.0 * p["head"] * new_tokens)
    scan = c["mamba"] * (6.0 * e * n + 2.0 * shape.mamba_d_conv * e) * (prompt_len + decoded)
    meet = 12.0 * hd * pairs  # a query and a key, a layer
    # keys the window layers' positions see: min(t + 1, window) at position t
    through = prompt_len + decoded
    full = min(through, w)
    seen = full * (full + 1) / 2 + (through - full) * w
    # the one cache: the prompt's last position, then every generated token
    shared = prompt_len + sum(prompt_len + i + 1 for i in range(decoded))
    return (matrices + scan + meet * (shape.periods_a * seen
                                      + (1 + c["cross"]) * shared))

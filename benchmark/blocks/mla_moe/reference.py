"""Block ``mla_moe``, plain reference: a decoder of multi-head latent
attention and routed experts with a shared expert, a norm before AND after
each sublayer, leading dense layers, an untied head; float32 ``jax.numpy``,
every product at ``Precision.HIGHEST``, no cache, no kernels. Written from
the published configuration of openPangu-Ultra-MoE-718B (``config.json``;
the equations are those of the latent attention and the sigmoid-scored
routing the configuration's keys name). With ``d`` the hidden size::

    x   = embed[t]
    a   = RMSNorm_in(x)
    c_q = RMSNorm_q(a W_dq)                     [q_nope | q_rope] = c_q W_uq   (a head)
    [c_kv | k_r] = a W_dkv                      c = RMSNorm_kv(c_kv)
    k_rope = RoPE(k_r) (one vector a token, shared by all heads);  q_rope = RoPE(q_rope)
    [k_nope | v] = c W_ukv                      (a head; up-projected for EVERY position)
    p   = causal softmax((q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope));  o = p v
    x   = x + RMSNorm_post_attn(o W_o)
    m   = RMSNorm_pre_mlp(x)
    f   = W_down(silu(W_gate m) * W_up m)                       the leading dense layers
    f   = sum_{i in top-k(s), i held} g_i E_i(m) + E_shared(m)  the layers after them
          s = sigmoid(m W_r) over ALL the router's experts, float32
          g_i = scaling * s_i / (sum_{j in top-k} s_j + 1e-20)
    x   = x + RMSNorm_post_mlp(f)
    logits = RMSNorm_final(x) W_head

**A chip's share.** The router keeps its published width
(``n_routed_experts_published``) and its experts a token; this chip holds
experts ``[expert_offset, expert_offset + n_routed_experts)`` and the sum
runs over the token's chosen experts that are held here. What the others
would add is left out, here as in the program, and that partial result
goes on to the next layer. The shared expert is every chip's alike.

It runs on the chip beside 7 GB of int8 weights at a 4,096 window: one
sequence, a layer after the other, attention a few heads at a time (scores
of 128 heads at once are 8.6 GB), the held experts one at a time (a layer's
16 are 3 GB in float32), each computed for every token and weighted by
nought where the token did not choose it.

Leaves are laid out **as the program stores them** (2-D ``(in, out)``
matrices, heads flattened into the output axis; expert stacks ``(experts,
in, out)``; a group a layer, ``layer_00`` .. , because the program this
block serves keeps its layers unrolled, each layer's weights an array of
its own), so that the program's half only renames: the harness holds this
tree and the program's at once, and a leaf that is copied is held twice::

    embed (V, d)   final_norm (d,)   head (d, V)
    every layer_NN: attn_norm, post_attn_norm, mlp_norm, post_mlp_norm (d,)
        q_norm (rq,)   kv_norm (rkv,)
        w_dq (d, rq)  w_uq (rq, H*(nope+rope))  w_dkv (d, rkv+rope)
        w_ukv (rkv, H*(nope+v))   w_o (H*v, d)
    the first_k_dense_replace leading ones: w_gate, w_up (d, ff)  w_down (ff, d)
    those after: router (d, E_all) [kind embed: float32 whatever the weights]
        experts_gate, experts_up (E, d, fe)  experts_down (E, fe, d)
        shared_gate, shared_up (d, fs)  shared_down (fs, d)

A serving weight is ``{"q": int8, "scale": float32 (..., 1, N)}`` as in
``gqa_swiglu``; ``weight_bits=4`` (the control) rounds it to int4.

Operation counts are what these equations need, whatever computes them:
2 operations a multiply-add; the prompt is up-projected (``W_ukv`` applied
to every prompt position, scores over ``nope + rope``, values over ``v``:
the causal triangle), a generated token reads the latents (``W_ukv``
absorbed into its query and its output: scores over ``rank + rope``,
values over ``rank``); the routed experts count the pairs this chip
expects, ``k * held / all`` a token.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmark.blocks.gqa_swiglu.reference import HIGHEST, linear, rms_norm, rope

MODES = ("serve",)
HEADS_AT_ONCE = 8  # attention's scores alive at once: 8 x S x S float32


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the equations need, under the published key names;
    ``n_routed_experts`` counts the experts held here."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_routed_experts_published: int
    expert_offset: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    rope_theta: float
    rms_norm_eps: float

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        return cls(**{f.name: config[f.name] for f in dataclasses.fields(cls)})

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


def _attention(q, k, v):
    """Causal softmax attention of one sequence, ``HEADS_AT_ONCE`` heads at
    a time. q, k (S, H, dq); v (S, H, dv)."""
    s, h, dq = q.shape
    per = HEADS_AT_ONCE if h % HEADS_AT_ONCE == 0 else 1
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))

    def some(args):
        qh, kh, vh = args  # (per, S, d)
        scores = jnp.einsum("hsd,htd->hst", qh, kh, precision=HIGHEST)
        scores = jnp.where(causal, scores / jnp.sqrt(jnp.float32(dq)), -1e30)
        return jnp.einsum(
            "hst,htd->hsd", jax.nn.softmax(scores, -1), vh, precision=HIGHEST)

    split = lambda t: t.transpose(1, 0, 2).reshape(h // per, per, s, -1)  # noqa: E731
    out = jax.lax.map(some, (split(q), split(k), split(v)))  # (H/per, per, S, dv)
    return out.reshape(h, s, -1).transpose(1, 0, 2).reshape(s, -1)


def attention_sublayer(x, lp, shape: Shape, lin):
    """``x + RMSNorm_post(LatentAttn(RMSNorm_in(x)))`` of one sequence."""
    s = x.shape[0]
    h, eps = shape.num_attention_heads, shape.rms_norm_eps
    nope, rd, rank = shape.qk_nope_head_dim, shape.qk_rope_head_dim, shape.kv_lora_rank
    a = rms_norm(x, lp["attn_norm"], eps)
    c_q = rms_norm(lin(a, lp["w_dq"]), lp["q_norm"], eps)
    q = lin(c_q, lp["w_uq"]).reshape(s, h, nope + rd)
    kv = lin(a, lp["w_dkv"])
    c = rms_norm(kv[:, :rank], lp["kv_norm"], eps)
    k_rope = rope(kv[:, None, rank:], shape.rope_theta)  # (S, 1, rope)
    q_rope = rope(q[..., nope:], shape.rope_theta)
    k_v = lin(c, lp["w_ukv"]).reshape(s, h, nope + shape.v_head_dim)
    k = jnp.concatenate(
        [k_v[..., :nope], jnp.broadcast_to(k_rope, (s, h, rd))], -1)
    o = _attention(jnp.concatenate([q[..., :nope], q_rope], -1), k, k_v[..., nope:])
    return x + rms_norm(lin(o, lp["w_o"]), lp["post_attn_norm"], eps)


def swiglu(m, w_gate, w_up, w_down, lin):
    return lin(jax.nn.silu(lin(m, w_gate)) * lin(m, w_up), w_down)


def routing_weights(m, router, shape: Shape, top_k: int | None = None):
    """(S, held): each token's weight ``g_i`` on the experts held here,
    nought where it did not choose them. Scores over ALL the router's
    experts, the ``top_k`` largest, normalised over those and scaled."""
    k = shape.num_experts_per_tok if top_k is None else top_k
    scores = jax.nn.sigmoid(jnp.matmul(m, router, precision=HIGHEST))
    top, which = jax.lax.top_k(scores, k)
    g = shape.routed_scaling_factor * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    every = jnp.sum(
        jax.nn.one_hot(which, scores.shape[-1], dtype=jnp.float32) * g[..., None], 1)
    lo = shape.expert_offset
    return every[:, lo:lo + shape.n_routed_experts]


def routed_ffn(m, lp, shape: Shape, lin):
    """The held experts' part of ``sum_i g_i E_i(m)`` plus the shared
    expert: one expert at a time, over every token."""
    weight = routing_weights(m, lp["router"], shape)  # (S, held)

    def one(acc, args):
        w, wg, wu, wd = args
        return acc + w[:, None] * swiglu(m, wg, wu, wd, lin), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (weight.T, lp["experts_gate"], lp["experts_up"], lp["experts_down"]))
    return routed + swiglu(
        m, lp["shared_gate"], lp["shared_up"], lp["shared_down"], lin)


def _layer(x, lp, shape: Shape, lin, routed: bool):
    x = attention_sublayer(x, lp, shape, lin)
    m = rms_norm(x, lp["mlp_norm"], shape.rms_norm_eps)
    if routed:
        f = routed_ffn(m, lp, shape, lin)
    else:
        f = swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"], lin)
    return x + rms_norm(f, lp["post_mlp_norm"], shape.rms_norm_eps)


def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


def hidden(params, tokens, shape: Shape, precision="float32", weight_bits=8):
    """Final-norm hidden states (S, d) of one sequence ``tokens`` (S,)."""
    lin = functools.partial(linear, precision=precision, weight_bits=weight_bits)
    x = params["embed"][tokens]
    for i in range(shape.num_hidden_layers):
        x = _layer(x, params[layer_name(i)], shape, lin,
                   routed=i >= shape.first_k_dense_replace)
    return rms_norm(x, params["final_norm"], shape.rms_norm_eps)


def logits(params, tokens, shape: Shape, positions=None, precision="float32",
           weight_bits=8):
    """Logits (P, V) of one sequence at ``positions`` (all when None)."""
    x = hidden(params, tokens, shape, precision, weight_bits)
    if positions is not None:
        x = x[positions]
    return linear(x, params["head"], precision, weight_bits)


def _attention_leaves(shape: Shape) -> dict:
    d, h = shape.hidden_size, shape.num_attention_heads
    rq, rkv = shape.q_lora_rank, shape.kv_lora_rank
    nope, rd, vd = shape.qk_nope_head_dim, shape.qk_rope_head_dim, shape.v_head_dim
    return {
        "attn_norm": ((d,), "norm"), "post_attn_norm": ((d,), "norm"),
        "mlp_norm": ((d,), "norm"), "post_mlp_norm": ((d,), "norm"),
        "q_norm": ((rq,), "norm"), "kv_norm": ((rkv,), "norm"),
        "w_dq": ((d, rq), "matrix"),
        "w_uq": ((rq, h * (nope + rd)), "matrix"),
        "w_dkv": ((d, rkv + rd), "matrix"),
        "w_ukv": ((rkv, h * (nope + vd)), "matrix"),
        "w_o": ((h * vd, d), "matrix"),
    }


def leaf_shapes(shape: Shape) -> dict:
    """name -> (dims, kind): top-level leaves and a group a layer. A router
    is of kind ``embed``: float32 whatever the weights' type."""
    d, ff, fe = shape.hidden_size, shape.intermediate_size, shape.moe_intermediate_size
    e, fs = shape.n_routed_experts, shape.n_shared_experts * fe
    spec = {
        "embed": ((shape.vocab_size, d), "embed"),
        "final_norm": ((d,), "norm"),
        "head": ((d, shape.vocab_size), "matrix"),
    }
    for i in range(shape.num_hidden_layers):
        if i < shape.first_k_dense_replace:
            ffn = dict(w_gate=((d, ff), "matrix"), w_up=((d, ff), "matrix"),
                       w_down=((ff, d), "matrix"))
        else:
            ffn = dict(
                router=((d, shape.n_routed_experts_published), "embed"),
                experts_gate=((e, d, fe), "matrix"), experts_up=((e, d, fe), "matrix"),
                experts_down=((e, fe, d), "matrix"),
                shared_gate=((d, fs), "matrix"), shared_up=((d, fs), "matrix"),
                shared_down=((fs, d), "matrix"))
        spec[layer_name(i)] = dict(_attention_leaves(shape), **ffn)
    return spec


def matmul_params(shape: Shape) -> dict:
    """Matrix parameters by where a token meets them."""
    d, h = shape.hidden_size, shape.num_attention_heads
    rq, rkv = shape.q_lora_rank, shape.kv_lora_rank
    nope, rd, vd = shape.qk_nope_head_dim, shape.qk_rope_head_dim, shape.v_head_dim
    fe = shape.moe_intermediate_size
    return {
        # every token: W_dq, W_uq, W_dkv, W_o
        "attention": d * rq + rq * h * (nope + rd) + d * (rkv + rd) + h * vd * d,
        "w_ukv": rkv * h * (nope + vd),
        "dense_ffn": 3 * d * shape.intermediate_size,
        "shared": 3 * d * shape.n_shared_experts * fe,
        "router": d * shape.n_routed_experts_published,
        "expert": 3 * d * fe,
        "head": d * shape.vocab_size,
    }


def total_params(shape: Shape) -> int:
    p = matmul_params(shape)
    d, ld, le = shape.hidden_size, shape.first_k_dense_replace, shape.expert_layers
    attn = p["attention"] + p["w_ukv"]
    norms = (ld + le) * (4 * d + shape.q_lora_rank + shape.kv_lora_rank) + d
    return (ld * (attn + p["dense_ffn"])
            + le * (attn + p["shared"] + p["router"]
                    + shape.n_routed_experts * p["expert"])
            + p["head"] + d * shape.vocab_size + norms)


def serve_flops(shape: Shape, prompt_len: int, new_tokens: int) -> float:
    """One request: its prompt and all but the last generated token pass
    through the layers; the head is applied once a generated token. The
    prompt's positions are up-projected and attend over ``nope + rope``
    and ``v`` a head (the causal triangle); a generated token's query and
    output absorb ``W_ukv`` and attend over the latents, ``rank + rope`` and
    ``rank`` a head, against everything before it."""
    p = matmul_params(shape)
    h, L = shape.num_attention_heads, shape.num_hidden_layers
    nope, rd, vd = shape.qk_nope_head_dim, shape.qk_rope_head_dim, shape.v_head_dim
    rank = shape.kv_lora_rank
    decoded = new_tokens - 1
    through = prompt_len + decoded
    pairs = (shape.num_experts_per_tok * shape.n_routed_experts
             / shape.n_routed_experts_published)  # held pairs a token, expected
    a_token = (
        L * p["attention"] + shape.first_k_dense_replace * p["dense_ffn"]
        + shape.expert_layers * (p["shared"] + p["router"] + pairs * p["expert"]))
    matrices = 2.0 * a_token * through + 2.0 * p["head"] * new_tokens
    # prompt: W_ukv on every position, then scores and values over the triangle
    prefill = (2.0 * p["w_ukv"] * prompt_len
               + 2.0 * h * (nope + rd + vd) * prompt_len * (prompt_len + 1) / 2)
    # a generated token: absorb (nope x rank) and un-absorb (rank x v) a
    # head, which are W_ukv's operations once; then the latents before it
    attended = decoded * prompt_len + decoded * (decoded + 1) / 2
    decode = (2.0 * p["w_ukv"] * decoded
              + 2.0 * h * (rank + rd + rank) * attended)
    return matrices + L * (prefill + decode)

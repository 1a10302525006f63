"""A decoder that reads its own memory twice (SambaY, arXiv:2507.06607).

``TransformerConfig(mb_per_layer=2)`` lays the layers out as
Phi-4-mini-flash-reasoning publishes them. With ``half = n_layers // 2``
every layer is ``h = x + Mixer_l(LN1(x)); out = h + MLP(LN2(h))`` and the
mixer is one of five kinds **by the layer's place**::

    l even, l <= half       Mamba-1 (state: s (N, E) and the last d_conv - 1 rows of u)
    l odd,  l <  half       attention over the sliding_window newest positions (a ring)
    l == half + 1           full causal attention: the ONE cache of K and V
    l even, l >= half + 2   Gated Memory Unit: W_out(silu(W_in x) * m), m = layer half's scan
                            output of the same token, before its gate
    l odd,  l >= half + 3   cross-attention: a query of its own on layer half + 1's K and V

Attention is differential (arXiv:2410.05258): query heads ``2i``, ``2i + 1``
are pair ``i`` and read KV pair ``i // (n_heads / kv_heads)``;
``a = P1 V - lambda P2 V`` with ``V = [v_2j | v_2j+1]``, then an RMSNorm over
the pair's ``2 * head_dim`` numbers times ``1 - lambda_init``. No positional
encoding anywhere: the Mamba layers carry the order.

**How it runs.** The layers are two ``nn.scan``\\ s over a period of two
blocks, ``layers_a`` (``half / 2`` x [Mamba, window]) and ``layers_b``
(x [GMU, cross]), with ``block_<half>`` and ``block_<half + 1>`` unrolled
between them, so every product of a scanned layer is read where it lies in
the stacked int8 weights (``ops.quant.int8_matmul``'s stacked form). The
cache is one collection of stacks with a leading layer axis and the slot axis
second (what ``serve.slots.write_slot`` splices under ``scan_layers``)::

    cache_index                  ()  / (slots,)       the depth of a sequence
    ssm_state                    (half/2 + 1, B, N, E)      float32
    conv_state                   (half/2 + 1, B, d_conv - 1, E)  float32
    window_key, window_value     (half/2, B, KV/2, ring, 2 * head_dim)   ring = sliding_window
    shared_key, shared_value     (1, B, KV/2, max_seq_len, 2 * head_dim)

The stacks ride the scans as carries, so a step writes only its new rows and
state. A row of K or V holds a KV **pair** as ``2 * head_dim`` contiguous
numbers, and a query head is padded to that width with zeros on the other
half (``[q_2i | 0]``, ``[0 | q_2i+1]``): its scores against the pair's row
are its own head's scores exactly, and ``ops.decode_attention`` reads the
ring and the shared cache in place as ``KV / 2`` heads of ``2 * head_dim``.
A KV pair's rows lie together (heads before positions): ten pairs are no
whole sublane tile, and a cache with them innermost the compiler relays
whole on its way into and out of every program.

**Prefill** is linear in the prompt: layers ``0 .. half`` and layer
``half + 1``'s K and V run over the whole (right-padded) prompt, and layer
``half + 1``'s attention and every later layer for the one position whose
logits are served. Positions past ``p_len`` leave the state untouched
(``delta = 0`` there), the convolution's tail is rows
``p_len - d_conv + 1 .. p_len - 1`` and the rings receive the prompt's last
``min(p_len, ring)`` rows at ``t % ring``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TransformerConfig,
    _int8_stacks,
    grouped_masked_attention,
)
from pytorch_distributed_training_tutorials_tpu.ops.selective_scan import (
    selective_scan,
    selective_step,
)


def layer_kind(cfg: TransformerConfig, l: int) -> str:
    """``mamba`` / ``window`` / ``full`` / ``gmu`` / ``cross``: layer
    ``l``'s mixer, by its place alone."""
    half = cfg.n_layers // 2
    if l % cfg.mb_per_layer == 0:
        return "mamba" if l <= half else "gmu"
    if l < half:
        return "window"
    return "full" if l == half + 1 else "cross"


def lambda_init(layer) -> jax.Array:
    """``0.8 - 0.6 exp(-0.3 l)``; ``layer`` may be traced (a scan's index)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def mamba_sizes(cfg: TransformerConfig) -> tuple[int, int, int, int]:
    """``(d_inner, d_state, dt_rank, d_conv)``."""
    rank = cfg.mamba_dt_rank or math.ceil(cfg.d_model / 16)
    return cfg.mamba_expand * cfg.d_model, cfg.mamba_d_state, rank, cfg.mamba_d_conv


def ring_rows(cfg: TransformerConfig) -> int:
    return min(cfg.sliding_window, cfg.max_seq_len)


def _sub(stacks, *names):
    """``stacks[a][b]...`` or None: a projection's ``(Int8Param, layer)``
    under the scan, by its module path."""
    for n in names:
        if not stacks:
            return None
        stacks = stacks.get(n)
    return stacks


def _dense(cfg, features: int, name: str, use_bias: bool, stacked=None):
    """The projection ``name``: ``nn.Dense``, or its int8 twin reading
    ``stacked`` (the scan's stack and this layer's index) in place."""
    if cfg.quantized:
        from pytorch_distributed_training_tutorials_tpu.ops.quant import (
            Int8Dense,
        )

        mod = Int8Dense(features, use_bias=use_bias, name=name)
        return lambda x: mod(x, stacked=stacked)
    return nn.Dense(features, use_bias=use_bias, dtype=cfg.dtype, name=name)


class LayerNorm(nn.Module):
    """LayerNorm with mean subtraction, weight and bias; stats in float32."""

    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        c = x32 - jnp.mean(x32, -1, keepdims=True)
        y = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + self.eps)
        return (y * scale + bias).astype(x.dtype)


class TiedEmbed(nn.Module):
    """The embedding that is also the head. Served in int8 the head is its
    own leaf and this table is what a lookup reads: it is kept in the
    compute type (``param_dtype``), because the compiler turns a lookup of
    float32 rows that are cast afterwards into a lookup in a cast table,
    and casts the whole table (2 GB here) on every launch."""

    vocab_size: int
    features: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.embedding = self.param(
            "embedding", nn.initializers.normal(1.0),
            (self.vocab_size, self.features), self.param_dtype,
        )

    def __call__(self, tokens):
        return jnp.take(self.embedding, tokens, axis=0).astype(self.dtype)

    def attend(self, x):
        return jnp.dot(x, self.embedding.astype(self.dtype).T)


class GatedMLP(nn.Module):
    """``W_d(up * silu(gate))`` with ``[gate | up] = W_gu z``: one fused
    product in, one out, no bias."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, z, stacks=None):
        cfg = self.cfg
        gu = _dense(cfg, 2 * cfg.ff_dim, "gate_up_proj", False,
                    _sub(stacks, "gate_up_proj"))(z)
        gate, up = gu[..., : cfg.ff_dim], gu[..., cfg.ff_dim :]
        return _dense(cfg, cfg.d_model, "down_proj", False,
                      _sub(stacks, "down_proj"))(up * nn.silu(gate))


def _rows_at(x, idx):
    """``x[b, idx[b]]`` for ``x`` (B, S, ...) and ``idx`` (B,) -> (B, 1, ...)."""
    return x[jnp.arange(x.shape[0]), idx][:, None]


def handed_on(y, gated):
    """What layer ``half`` hands the Gated Memory Units: its scan output
    ``y`` BEFORE the gate ``silu(z)``, not ``gated`` (a test plants the
    other and sees the comparison fail)."""
    del gated
    return y


class MambaMixer(nn.Module):
    """Mamba-1: ``[u | z] = W_in x``; ``u' = silu(conv(u))`` (causal,
    depthwise, ``d_conv`` taps, bias); ``[dr | B | C] = W_x u'``;
    ``delta = softplus(W_dt dr + b_dt)``; ``A = -exp(A_log)``;
    ``s_t = exp(delta_t A) s_{t-1} + (delta_t u'_t) B_t^T``;
    ``y_t = s_t C_t + D u'_t``; result ``W_out(y * silu(z))``.

    Returns ``(result, y, state)``: ``y`` is what layer ``half`` hands the
    Gated Memory Units, ``state`` the pair ``(s (B, N, E), the last
    d_conv - 1 rows of u (B, d_conv - 1, E))`` in float32: with ``p_len``
    ((B,): a prefill) after the last position inside it, with a ``state``
    handed in (``x`` is one position) that state stepped, else None."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, state=None, p_len=None, stacks=None):
        cfg = self.cfg
        e, n, rank, taps = mamba_sizes(cfg)
        b, s = x.shape[0], x.shape[1]
        uz = _dense(cfg, 2 * e, "in_proj", False, _sub(stacks, "in_proj"))(x)
        u = uz[..., :e].astype(jnp.float32)
        z = uz[..., e:]
        conv_w = self.param(
            "conv_weight", nn.initializers.lecun_normal(), (taps, e)
        )
        conv_b = self.param("conv_bias", nn.initializers.zeros, (e,))
        # rows t - taps + 1 .. t of u under tap 0 .. taps - 1
        with jax.named_scope("ssm_conv"):
            if state is not None:
                hist = jnp.concatenate([state[1], u], 1)  # (B, taps, E)
                new_tail = hist[:, 1:]
            else:
                hist = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
                new_tail = None
                if p_len is not None:
                    # rows p_len - taps + 1 .. p_len - 1 of u: rows
                    # p_len .. p_len + taps - 2 of the padded array
                    new_tail = jax.vmap(
                        lambda h, at: jax.lax.dynamic_slice_in_dim(
                            h, at, taps - 1, 0
                        )
                    )(hist, p_len)
            conv = sum(
                hist[:, k : k + s] * conv_w[k] for k in range(taps)
            ) + conv_b
            up = nn.silu(conv)  # (B, S, E) float32
        dbc = _dense(cfg, rank + 2 * n, "x_proj", False,
                     _sub(stacks, "x_proj"))(up.astype(x.dtype))
        dr, bm, cm = jnp.split(
            dbc.astype(jnp.float32), [rank, rank + n], axis=-1
        )
        delta = nn.softplus(
            _dense(cfg, e, "dt_proj", True, _sub(stacks, "dt_proj"))(
                dr.astype(x.dtype)
            ).astype(jnp.float32)
        )
        a_log = self.param(
            "A_log",
            lambda key, shape: jnp.log(jnp.broadcast_to(
                jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], shape
            )),
            (n, e),
        )
        d_skip = self.param("D", nn.initializers.ones, (e,))
        a = -jnp.exp(a_log)
        with jax.named_scope("ssm_scan"):
            if state is not None:
                s_new, y = selective_step(
                    state[0], up[:, 0], delta[:, 0], a, bm[:, 0], cm[:, 0]
                )
                y = y[:, None]
            else:
                if p_len is not None:
                    # a position past the prompt leaves the state where it was
                    inside = jnp.arange(s)[None, :] < p_len[:, None]
                    delta = jnp.where(inside[..., None], delta, 0.0)
                y, s_new = selective_scan(up, delta, a, bm, cm)
            y = y + d_skip * up
        gated = y * nn.silu(z.astype(jnp.float32))
        out = _dense(cfg, cfg.d_model, "out_proj", False,
                     _sub(stacks, "out_proj"))(gated.astype(x.dtype))
        keep = state is not None or p_len is not None
        return out, handed_on(y, gated), (
            (s_new, new_tail) if keep else None
        )


class GatedMemoryUnit(nn.Module):
    """``W_out(silu(W_in x) * m)``: ``m`` is layer ``half``'s scan output
    of the same token."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, m, stacks=None):
        cfg = self.cfg
        e = mamba_sizes(cfg)[0]
        with jax.named_scope("gmu"):
            g = _dense(cfg, e, "in_proj", False, _sub(stacks, "in_proj"))(x)
            mixed = (nn.silu(g.astype(jnp.float32)) * m).astype(x.dtype)
            return (_dense(cfg, cfg.d_model, "out_proj", False,
                           _sub(stacks, "out_proj"))(mixed),)


def pair_queries(q: jax.Array, dtype) -> jax.Array:
    """(B, S, H, hd) -> (B, S, H, 2 hd): an even head on the first half,
    an odd one on the second, zeros on the other, times ``sqrt(2)`` so that
    an attention that divides by ``sqrt(2 hd)`` divides by ``sqrt(hd)``."""
    b, s, h, hd = q.shape
    q = q.astype(jnp.float32).reshape(b, s, h // 2, 2, hd) * math.sqrt(2.0)
    zero = jnp.zeros_like(q[:, :, :, 0])
    even = jnp.concatenate([q[:, :, :, 0], zero], -1)
    odd = jnp.concatenate([zero, q[:, :, :, 1]], -1)
    return jnp.stack([even, odd], 3).reshape(b, s, h, 2 * hd).astype(dtype)


def banded_attention(q, k, v, window: int | None):
    """Causal attention of whole sequences, each position over the
    ``window`` newest (itself included; None: all before it). q (B, S, H,
    D), k and v (B, S, KV, D). Where the sequence is several windows long,
    a block of ``window`` queries meets its own block of keys and the one
    before it, and no others."""
    b, s, h, d = q.shape
    if window is None or s <= window or s % window:
        t = jnp.arange(s)
        mask = t[None, :] <= t[:, None]
        if window is not None:
            mask = mask & (t[None, :] > t[:, None] - window)
        return grouped_masked_attention(q, k, v, mask[None, None])
    nb = s // window
    kv = k.shape[2]

    def blocks(t, heads):
        return t.reshape(b, nb, window, heads, d)

    def with_previous(t):
        t = blocks(t, kv)
        prev = jnp.pad(t[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * 3)
        return jnp.concatenate([prev, t], 2)  # (B, nb, 2 window, KV, D)

    i = jnp.arange(window)[:, None]
    j = jnp.arange(2 * window)[None, :]
    # key j of [previous | own] lies window - j + i positions back
    mask = (j <= i + window) & (j > i)
    first = mask & (j >= window)  # block 0 has nothing before it

    def one(qb, kb, vb, m):
        return grouped_masked_attention(qb, kb, vb, m[None, None])

    kk, vv = with_previous(k), with_previous(v)
    masks = jnp.where(
        (jnp.arange(nb) == 0)[:, None, None], first[None], mask[None]
    )
    out = jax.vmap(one, in_axes=(1, 1, 1, 0), out_axes=1)(
        blocks(q, h), kk, vv, masks
    )
    return out.reshape(b, s, h, d)


def _cached_attention(q_pad, k_stack, v_stack, layer, depth, scope: str):
    """One query position a row over rows ``[0, depth]`` of
    ``k_stack[layer]`` / ``v_stack[layer]`` (L, B, KVp, W, D); ``depth >=
    W``: a row that holds nothing. The decode kernel reads the stack in
    place where it takes the shapes; else plain einsums over a copy."""
    from pytorch_distributed_training_tutorials_tpu.ops.decode_attention import (
        decode_attention,
        decode_block,
    )

    _, b, kvp, w, d = k_stack.shape
    with jax.named_scope(scope):
        if decode_block(w, kvp, d, k_stack.dtype):
            with jax.named_scope("decode_attn"):  # the kernel, as Attention's
                return decode_attention(
                    q_pad[:, 0], k_stack, v_stack, layer, depth,
                    heads_major=True,
                )[:, None]
        with jax.named_scope("kv_cache"):
            k_read = jnp.swapaxes(k_stack[layer], 1, 2)
            v_read = jnp.swapaxes(v_stack[layer], 1, 2)
        mask = (jnp.arange(w)[None, :] <= depth[:, None]) & (
            depth[:, None] < w
        )
        return grouped_masked_attention(
            q_pad, k_read, v_read, mask[:, None, None, :]
        )


@jax.named_scope("kv_cache")
def _write_rows(stack, layer, rows, at):
    """``stack[layer, b, :, at[b]] = rows[b, 0]``; ``at`` past the rows
    drops."""
    b, kvp = rows.shape[0], rows.shape[2]
    # one index a (row, KV pair): an update is one lane row of the stack as
    # it lies (a window over the KV pairs makes the compiler relay the
    # whole stack around the scatter, on every layer of every step)
    return stack.at[
        layer, jnp.arange(b)[:, None], jnp.arange(kvp)[None, :], at[:, None]
    ].set(rows[:, 0].astype(stack.dtype), mode="drop")


@jax.named_scope("kv_cache")
def _write_ring(stack, layer, rows, p_len):
    """The ring of a prefilled prompt: place ``r`` receives the newest
    position ``t < p_len`` with ``t % ring == r`` of ``rows`` (B, S, ...);
    a place no position has reached holds what depth masks."""
    ring = stack.shape[3]
    b, s = rows.shape[0], rows.shape[1]
    r = jnp.arange(ring)[None, :]
    t = r + ring * ((p_len[:, None] - 1 - r) // ring)  # (B, ring); < 0: none yet
    picked = jnp.take_along_axis(
        rows, jnp.clip(t, 0, s - 1).reshape(b, ring, 1, 1), axis=1
    )
    return stack.at[layer].set(
        jnp.swapaxes(picked, 1, 2).astype(stack.dtype)
    )


class DiffAttention(nn.Module):
    """Differential attention of one of three kinds: ``window`` (its own K
    and V in a ring of ``sliding_window`` rows), ``full`` (its own K and V
    over the whole window: the shared cache) and ``cross`` (a query alone,
    on the shared cache).

    ``kv`` says what the apply is. None: whole sequences, no cache (K and V
    returned for the cross layers). ``("prefill", k_stack, v_stack, layer,
    p_len)``: whole sequences, the stacks written (``window``: attention
    over the sequence; ``full``: the one position ``p_len - 1`` against the
    written cache). ``("step", k_stack, v_stack, layer, pos)``: one position
    a row at depth ``pos``. ``cross`` takes ``("seq", k, v)`` or
    ``("step", ...)`` and writes nothing. Returns ``(result, k_stack,
    v_stack)`` (``(result, k, v)`` for a sequence without cache)."""

    cfg: TransformerConfig
    kind: str

    @nn.compact
    def __call__(self, x, kv, layer, stacks=None):
        cfg, kind = self.cfg, self.kind
        h, kvh, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        b, s = x.shape[0], x.shape[1]
        if kind == "cross":
            q = _dense(cfg, h * hd, "q_proj", True, _sub(stacks, "q_proj"))(x)
        else:
            qkv = _dense(cfg, (h + 2 * kvh) * hd, "qkv_proj", True,
                         _sub(stacks, "qkv_proj"))(x)
            q = qkv[..., : h * hd]
            # a KV pair's [k_2j | k_2j+1] are 2 hd contiguous numbers
            k = qkv[..., h * hd : (h + kvh) * hd].reshape(
                b, s, kvh // 2, 2 * hd
            )
            v = qkv[..., (h + kvh) * hd :].reshape(b, s, kvh // 2, 2 * hd)
        q = q.reshape(b, s, h, hd)
        mode = None if kv is None else kv[0]
        window = ring_rows(cfg) if kind == "window" else None
        scope = "window_attn" if kind == "window" else "shared_kv_attn"
        k_out = v_out = None
        if mode == "step":
            _, k_stack, v_stack, at, pos = kv
            depth = pos
            if kind == "window":
                ring = k_stack.shape[3]
                # a parked row (pos == the window) reads nothing, and what
                # it writes lies in a slot a refill rewrites whole
                depth = jnp.where(
                    pos >= cfg.max_seq_len, ring, jnp.minimum(pos, ring - 1)
                )
                k_stack = _write_rows(k_stack, at, k, pos % ring)
                v_stack = _write_rows(v_stack, at, v, pos % ring)
            elif kind == "full":
                k_stack = _write_rows(k_stack, at, k, pos)
                v_stack = _write_rows(v_stack, at, v, pos)
            out = _cached_attention(
                pair_queries(q, k_stack.dtype), k_stack, v_stack, at, depth,
                scope,
            )
            k_out, v_out = k_stack, v_stack
        elif kind == "cross":
            _, k, v = kv
            with jax.named_scope(scope):
                out = banded_attention(pair_queries(q, x.dtype), k, v, None)
        elif kind == "full" and mode == "prefill":
            _, k_stack, v_stack, at, p_len = kv
            with jax.named_scope("kv_cache"):
                k_stack, v_stack = (
                    jax.lax.dynamic_update_slice(
                        stack, jnp.swapaxes(t, 1, 2).astype(stack.dtype)[None],
                        (at, 0, 0, 0, 0),
                    )
                    for stack, t in ((k_stack, k), (v_stack, v))
                )
            last = p_len - 1
            out = _cached_attention(
                pair_queries(_rows_at(q, last), k_stack.dtype),
                k_stack, v_stack, at, last, scope,
            )
            k_out, v_out = k_stack, v_stack
        else:
            with jax.named_scope(scope):
                out = banded_attention(pair_queries(q, x.dtype), k, v, window)
            k_out, v_out = k, v
            if mode == "prefill":
                _, k_stack, v_stack, at, p_len = kv
                k_out = _write_ring(k_stack, at, k, p_len)
                v_out = _write_ring(v_stack, at, v, p_len)
        # the differential combination, a pair at a time
        lam = {
            n: self.param(n, nn.initializers.normal(0.1), (hd,))
            for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
        }
        sub_w = self.param("subln", nn.initializers.ones, (2 * hd,))
        with jax.named_scope("diff_combine"):
            init = lambda_init(layer)
            lam_full = (
                jnp.exp(jnp.sum(lam["lambda_q1"] * lam["lambda_k1"]))
                - jnp.exp(jnp.sum(lam["lambda_q2"] * lam["lambda_k2"]))
                + init
            )
            o = out.astype(jnp.float32).reshape(
                b, out.shape[1], h // 2, 2, 2 * hd
            )
            a = o[:, :, :, 0] - lam_full * o[:, :, :, 1]
            a = a * jax.lax.rsqrt(
                jnp.mean(a * a, -1, keepdims=True) + cfg.norm_eps
            ) * sub_w * (1.0 - init)
            a = a.reshape(b, out.shape[1], h * hd).astype(x.dtype)
        y = _dense(cfg, cfg.d_model, "o_proj", True, _sub(stacks, "o_proj"))(a)
        return y, k_out, v_out


class SambaBlock(nn.Module):
    """``h = x + Mixer(LN1(x)); out = h + MLP(LN2(h))`` of one kind; what
    the mixer takes beside its input comes as ``mixer_args``. Returns
    ``(out, *what else the mixer gave)``. ``last`` (prefill of the
    full-attention layer): the mixer answered for that one position a row,
    and the rest of the block, as of the model, follows it alone."""

    cfg: TransformerConfig
    kind: str

    @nn.compact
    def __call__(self, x, *mixer_args, stacks=None, last=None, **mixer_kw):
        cfg = self.cfg
        if self.kind == "mamba":
            mixer = MambaMixer(cfg, name="mixer")
        elif self.kind == "gmu":
            mixer = GatedMemoryUnit(cfg, name="mixer")
        else:
            mixer = DiffAttention(cfg, self.kind, name="attn")
        y, *extra = mixer(
            LayerNorm(cfg.norm_eps, name="ln1")(x), *mixer_args,
            stacks=_sub(stacks, mixer.name), **mixer_kw,
        )
        if last is not None:
            x = _rows_at(x, last)
        h = x + y
        out = h + GatedMLP(cfg, name="mlp")(
            LayerNorm(cfg.norm_eps, name="ln2")(h), _sub(stacks, "mlp")
        )
        return (out, *extra)


@jax.named_scope("kv_cache")
def _store_state(cache, i, state):
    s, tail = state
    return dict(
        cache,
        ssm_state=cache["ssm_state"].at[i].set(s),
        conv_state=cache["conv_state"].at[i].set(tail),
    )


def _mamba_block(cfg, name, x, cache, i, mode, p_len, stacks):
    """Block of kind ``mamba`` at place ``i`` of the state stacks. Returns
    ``(out, y, cache)``."""
    state = None
    if mode == "step":
        state = cache["ssm_state"][i], cache["conv_state"][i]
    out, y, new = SambaBlock(cfg, "mamba", name=name)(
        x, state=state, p_len=p_len, stacks=stacks,
    )
    if new is not None:
        cache = _store_state(cache, i, new)
    return out, y, cache


def _attn_kv(mode, k_stack, v_stack, at, pos, p_len):
    """What a layer with K and V of its own is told (``DiffAttention``)."""
    if mode == "step":
        return ("step", k_stack, v_stack, at, pos)
    if mode == "prefill":
        return ("prefill", k_stack, v_stack, at, p_len)
    return None


def _layer_stacks(stacks, i):
    """Each stacked ``Int8Param`` paired with the layer's index."""
    if stacks is None:
        return None
    from pytorch_distributed_training_tutorials_tpu.ops.quant import Int8Param

    return jax.tree_util.tree_map(
        lambda w: (w, i), stacks, is_leaf=lambda t: isinstance(t, Int8Param)
    )


class _PairA(nn.Module):
    """One period of ``layers_a``: a Mamba block, then a window block."""

    cfg: TransformerConfig
    mode: str | None

    @nn.compact
    @jax.named_scope("layers")  # what layer_scan holds besides is the scan's own slicing
    def __call__(self, carry, stacks, ctx, i):
        cfg, mode = self.cfg, self.mode
        x, cache = carry
        pos, p_len = ctx
        stacks = _layer_stacks(stacks, i)
        x, _, cache = _mamba_block(
            cfg, "mamba_block", x, cache, i, mode, p_len,
            _sub(stacks, "mamba_block"),
        )
        kv = None
        if mode is not None:
            kv = _attn_kv(
                mode, cache["window_key"], cache["window_value"], i, pos, p_len
            )
        x, k, v = SambaBlock(cfg, "window", name="window_block")(
            x, kv, 2 * i + 1, stacks=_sub(stacks, "window_block")
        )
        if mode is not None:
            cache = dict(cache, window_key=k, window_value=v)
        return (x, cache), None


class _PairB(nn.Module):
    """One period of ``layers_b``: a Gated Memory Unit block, then a
    cross-attention block on the shared K and V."""

    cfg: TransformerConfig
    mode: str | None

    @nn.compact
    @jax.named_scope("layers")
    def __call__(self, x, stacks, ctx, i):
        cfg = self.cfg
        m, kv = ctx
        stacks = _layer_stacks(stacks, i)
        x, = SambaBlock(cfg, "gmu", name="gmu_block")(
            x, m, stacks=_sub(stacks, "gmu_block")
        )
        x, _, _ = SambaBlock(cfg, "cross", name="cross_block")(
            x, kv, cfg.n_layers // 2 + 3 + 2 * i,
            stacks=_sub(stacks, "cross_block"),
        )
        return x, None


def _cache_vars(mod: nn.Module, cfg: TransformerConfig, b: int, dtype):
    """The cache collection's variables, created as zeros where the apply
    was handed none."""
    e, n, _, taps = mamba_sizes(cfg)
    half = cfg.n_layers // 2
    n_a = half // 2
    kvp, width = cfg.kv_heads // 2, 2 * cfg.head_dim
    if cfg.kv_cache_dtype is not None:
        dtype = jnp.dtype(cfg.kv_cache_dtype)
    spec = {
        "ssm_state": ((n_a + 1, b, n, e), jnp.float32),
        "conv_state": ((n_a + 1, b, taps - 1, e), jnp.float32),
        "window_key": ((n_a, b, kvp, ring_rows(cfg), width), dtype),
        "window_value": ((n_a, b, kvp, ring_rows(cfg), width), dtype),
        "shared_key": ((1, b, kvp, cfg.max_seq_len, width), dtype),
        "shared_value": ((1, b, kvp, cfg.max_seq_len, width), dtype),
    }
    var = {
        name: mod.variable("cache", name, jnp.zeros, shape, dt)
        for name, (shape, dt) in spec.items()
    }
    var["cache_index"] = mod.variable(
        "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
    )
    return var


def forward(mod: nn.Module, tokens, decode: bool, prefill: bool,
            return_hidden: bool, last_pos):
    """``TransformerLM.__call__`` for ``cfg.mb_per_layer > 0``; ``mod`` is
    the ``TransformerLM`` whose compact call this runs inside, so the
    submodules made here are its own."""
    cfg = mod.cfg
    half = cfg.n_layers // 2
    n_a, n_b = half // 2, (cfg.n_layers - half - 2) // 2
    b, s = tokens.shape
    mode = "step" if decode else ("prefill" if prefill else None)
    if decode and s != 1:
        raise ValueError(
            "a model with recurrent state steps one position at a time: a "
            f"decode chunk of {s} positions (suffix or chunked prefill, a "
            "speculative verify) has no state to rewind to"
        )
    embed = TiedEmbed(
        cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
        param_dtype=cfg.dtype if cfg.quantized else jnp.float32,
        name="tok_emb",
    )
    x = embed(tokens)
    cache = pos = p_len = None
    if mode is not None:
        var = _cache_vars(mod, cfg, b, x.dtype)
        cache = {
            name: v.value for name, v in var.items() if name != "cache_index"
        }
        if decode:
            pos = jnp.broadcast_to(var["cache_index"].value, (b,))
        else:
            prompt = jnp.asarray(
                s if last_pos is None else jnp.asarray(last_pos) + 1,
                jnp.int32,
            )
            p_len = jnp.broadcast_to(prompt, (b,))

    def scan(cell, name, length):
        stacks = None
        if cfg.quantized:
            stacks = _int8_stacks(
                mod.variables.get("params", {}).get(name, {})
            )
        run = nn.scan(
            cell, variable_axes={"params": 0}, split_rngs={"params": True},
            in_axes=(nn.broadcast, nn.broadcast, 0), length=length,
        )(cfg, mode, name=name)
        return lambda carry, ctx: run(
            carry, stacks, ctx, jnp.arange(length)
        )[0]

    with jax.named_scope("layer_scan"):
        x, cache = scan(_PairA, "layers_a", n_a)((x, cache), (pos, p_len))
    x, m, cache = _mamba_block(
        cfg, f"block_{half}", x, cache, n_a, mode, p_len, None
    )
    last = kv = None
    if mode == "prefill":
        last = p_len - 1
        m = _rows_at(m, last)
    if mode is not None:
        kv = _attn_kv(
            mode, cache["shared_key"], cache["shared_value"], 0, pos, p_len
        )
    x, k, v = SambaBlock(cfg, "full", name=f"block_{half + 1}")(
        x, kv, half + 1, last=last
    )
    if mode is None:
        shared = ("seq", k, v)
    else:
        cache = dict(cache, shared_key=k, shared_value=v)
        shared = ("step", k, v, 0, pos if decode else last)
    with jax.named_scope("layer_scan"):
        x = scan(_PairB, "layers_b", n_b)(x, (m, shared))
    if mode is not None:
        for name, value in cache.items():
            var[name].value = value
        var["cache_index"].value = (
            var["cache_index"].value + 1 if decode
            else jnp.broadcast_to(prompt, var["cache_index"].value.shape)
        )
    x = LayerNorm(cfg.norm_eps, name="final_norm")(x)
    if return_hidden:
        return x
    if cfg.quantized:
        from pytorch_distributed_training_tutorials_tpu.ops.quant import (
            Int8Dense,
        )

        return Int8Dense(cfg.vocab_size, use_bias=False, name="lm_head")(x)
    return embed.attend(x)  # tied: logits = LN_f(x) E^T

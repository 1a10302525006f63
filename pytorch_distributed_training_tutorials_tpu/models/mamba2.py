"""The Mamba-2 mixer of a block whose mixer is two branches (Falcon-H1).

``TransformerConfig(mamba_n_heads=H, ...)`` gives every ``Block`` this mixer
beside its attention, on the same normed input ``u``, and sums the two
(``Block`` in ``models/transformer.py``). With ``I = H * P`` channels (``P =
mamba_d_head``), ``G = mamba_n_groups`` groups, ``N = mamba_d_state``
states and ``K = mamba_d_conv`` taps::

    p = [W_in | W_dt] u * (ssm_in_multiplier * [s0 x I | s1 x I | s2 x GN | s3 x GN | s4 x H])
    [z | xBC | dt] = split(p, [I, I + 2GN, H])
    xBC_t = silu(sum_k w_conv[k] * xBC_{t-K+1+k} + b_conv)        causal, depthwise
    [x | B | C] = split(xBC, [I, GN, GN]);  delta = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t[h] = exp(delta_t[h] A[h]) h_{t-1}[h] + B_t[g(h)] (delta_t[h] x_t[h])^T     (N, P)
    y_t[h] = C_t[g(h)]^T h_t[h] + D[h] x_t[h]
    y = w_norm * GroupRMS_G(y * silu(z))          the gate first, then the norm a group
    result = ssm_out_multiplier * W_out y

``W (c u) = c (W u)``: the input's multiplier rides with the five on the
projection's result, one vector of ``2I + 2GN + H`` numbers.

**The cache** is three variables a layer (stacked ``(L, ...)`` by the layer
scan, the slot axis second: what ``serve.slots.write_slot`` splices)::

    ssm_state    (B, H, N, P)      float32
    conv_state   (B, K - 1, I + 2GN)   float32: the last K - 1 rows of xBC before the convolution
    cache_index  () / (B,)         this mixer's copy of the sequence's depth

The depth is here for one thing: ``park_cache_index`` sets it to the window
where a slot holds no live sequence, and ``ops.ssd.ssd_update`` then
neither reads nor writes that slot's state.

**A step** runs ``ops.ssd.ssd_update`` on the carried stack in place (plain
``ssd_step`` on a copy of the layer's slice where the kernel does not take
the sizes: toy widths). **A prefill** runs the chunked form
(``ops.ssd.ssd_chunked``) over the right-padded bucket: past ``p_len``
``delta = 0`` leaves the state where the last real position put it, and the
convolution's tail is rows ``p_len - K + 1 .. p_len - 1``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TransformerConfig,
    _layer_value,
    _store_cache_index,
    _update_slice,
)
from pytorch_distributed_training_tutorials_tpu.ops.ssd import (
    ssd_chunked,
    ssd_heads_block,
    ssd_step,
    ssd_update,
)


def mamba2_sizes(cfg: TransformerConfig) -> tuple[int, int, int, int, int, int]:
    """``(H, P, G, N, K, conv_dim)``; ``conv_dim = H P + 2 G N``."""
    h, p, g = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups
    n, k = cfg.mamba_d_state, cfg.mamba_d_conv
    return h, p, g, n, k, h * p + 2 * g * n


def gate_and_norm(y, z, weight, groups: int, eps: float):
    """``w * GroupRMS(y * silu(z))``: the gate first, then the mean square
    over each of ``groups`` runs of channels (a test plants the other order
    and sees the comparison fail)."""
    gated = y * nn.silu(z)
    shape = gated.shape
    grouped = gated.reshape(*shape[:-1], groups, shape[-1] // groups)
    normed = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + eps
    )
    return normed.reshape(shape) * weight


def _dense(cfg, features: int, name: str, stacked):
    """The projection ``name`` without bias: ``nn.Dense``, or its int8 twin
    reading ``stacked`` (the scan's stack and this layer's index) in place."""
    if cfg.quantized:
        from pytorch_distributed_training_tutorials_tpu.ops.quant import (
            Int8Dense,
        )

        mod = Int8Dense(features, use_bias=False, name=name)
        return lambda x: mod(x, stacked=stacked)
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, name=name)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 branch of a parallel block; ``u`` (B, S, d) in, (B, S, d)
    out. ``decode``: one position a row against the cache; ``prefill``:
    whole right-padded sequences of real lengths ``p_len`` (B,), the cache
    written; neither: whole sequences, no cache. ``layer`` and ``stacks``
    as ``Attention``'s."""

    cfg: TransformerConfig

    def _cache_vars(self, b: int):
        h, p, _, n, k, conv_dim = mamba2_sizes(self.cfg)
        state = self.variable(
            "cache", "ssm_state", jnp.zeros, (b, h, n, p), jnp.float32
        )
        tail = self.variable(
            "cache", "conv_state", jnp.zeros, (b, k - 1, conv_dim), jnp.float32
        )
        idx = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        return state, tail, idx

    @nn.compact
    def __call__(self, u, decode: bool = False, prefill: bool = False,
                 p_len=None, layer=None, stacks=None):
        cfg = self.cfg
        stacks = stacks or {}
        h, p, g, n, taps, conv_dim = mamba2_sizes(cfg)
        inner = h * p
        b, s = u.shape[0], u.shape[1]
        # W_in's 2I + 2GN + H columns are two matrices: the H columns of dt
        # (32 at the published sizes) make the whole no whole number of
        # 128-lane tiles, and the compiler then relays the stacked int8
        # weights on their way into every launch
        *mult, dt_mult = (cfg.ssm_in_multiplier * m for m in cfg.ssm_multipliers)
        mult = jnp.concatenate([
            jnp.full((width,), m, jnp.float32)
            for width, m in zip((inner, inner, g * n, g * n), mult)
        ])
        proj = _dense(
            cfg, inner + conv_dim, "in_proj", stacks.get("in_proj")
        )(u).astype(jnp.float32) * mult
        z, xbc = jnp.split(proj, [inner], axis=-1)
        dt = _dense(cfg, h, "dt_proj", stacks.get("dt_proj"))(u).astype(
            jnp.float32
        ) * dt_mult
        conv_w = self.param(
            "conv_weight", nn.initializers.lecun_normal(), (taps, conv_dim)
        )
        conv_b = self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
        if decode or prefill:
            state_var, tail_var, idx = self._cache_vars(b)
        # rows t - taps + 1 .. t of xBC under tap 0 .. taps - 1
        with jax.named_scope("ssm_conv"):
            if decode:
                hist = jnp.concatenate(
                    [_layer_value(tail_var, layer), xbc], 1
                )  # (B, taps, conv_dim)
                _update_slice(tail_var, hist[:, 1:], (0, 0, 0), layer)
            else:
                hist = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
                if prefill:
                    # rows p_len - taps + 1 .. p_len - 1 of xBC: rows
                    # p_len .. p_len + taps - 2 of the padded array
                    _update_slice(tail_var, jax.vmap(
                        lambda rows, at: jax.lax.dynamic_slice_in_dim(
                            rows, at, taps - 1, 0
                        )
                    )(hist, p_len), (0, 0, 0), layer)
            xbc = nn.silu(sum(
                hist[:, k : k + s] * conv_w[k] for k in range(taps)
            ) + conv_b)
        x, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
        x = x.reshape(b, s, h, p)
        bm, cm = bm.reshape(b, s, g, n), cm.reshape(b, s, g, n)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,))
        a_log = self.param("A_log", nn.initializers.zeros, (h,))
        d_skip = self.param("D", nn.initializers.ones, (h,))
        delta = nn.softplus(dt + dt_bias)  # (B, S, H)
        a = -jnp.exp(a_log)
        with jax.named_scope("ssm_scan"):
            if decode:
                pos = jnp.broadcast_to(_layer_value(idx, layer), (b,))
                decay = jnp.exp(delta[:, 0] * a)
                dtx = delta[:, 0, :, None] * x[:, 0]
                if ssd_heads_block(h // g, n, p):
                    # the state is read and written where it lies in the
                    # carried stack; a slot whose depth is the window
                    # (park_cache_index) is not touched
                    stack = state_var.value
                    y, stack = ssd_update(
                        stack if layer is not None else stack[None],
                        0 if layer is None else layer,
                        decay, dtx, bm[:, 0], cm[:, 0], pos, cfg.max_seq_len,
                    )
                    state_var.value = stack if layer is not None else stack[0]
                else:
                    new, y = ssd_step(
                        _layer_value(state_var, layer), decay, dtx,
                        bm[:, 0], cm[:, 0],
                    )
                    _update_slice(state_var, new, (0, 0, 0, 0), layer)
                y = y[:, None]
                _store_cache_index(idx, _layer_value(idx, layer) + 1, layer)
            else:
                if prefill:
                    # a position past the prompt leaves the state where it was
                    inside = jnp.arange(s)[None, :] < p_len[:, None]
                    delta = jnp.where(inside[..., None], delta, 0.0)
                y, last = ssd_chunked(
                    x, delta, a, bm, cm, cfg.mamba_chunk_size
                )
                if prefill:
                    _update_slice(state_var, last, (0, 0, 0, 0), layer)
                    _store_cache_index(
                        idx, jnp.asarray(s, jnp.int32), layer
                    )
            y = y + d_skip[:, None] * x
        norm_w = self.param("norm_scale", nn.initializers.ones, (inner,))
        with jax.named_scope("ssm_gate_norm"):
            y = gate_and_norm(
                y.reshape(b, s, inner), z, norm_w, g, cfg.norm_eps
            ).astype(u.dtype)
        out = _dense(cfg, cfg.d_model, "out_proj", stacks.get("out_proj"))(y)
        if cfg.ssm_out_multiplier != 1.0:
            out = out * jnp.asarray(cfg.ssm_out_multiplier, out.dtype)
        return out

"""The state-space recurrence of a Mamba-2 layer (SSD, arXiv:2405.21060).

With ``H`` heads of ``P`` channels, ``N`` states, ``G`` groups of ``H / G``
heads that share ``B`` and ``C``, a scalar decay a head and a state
``h (N, P)`` a head and a sequence (``P`` on the lanes)::

    h_t = exp(dt_t A) h_{t-1} + B_t (dt_t x_t)^T        y_t = C_t^T h_t

``dt_t = 0`` is the identity on the state, to the last bit (``exp(0) = 1``,
``0 * x = 0``): that is how a right-padded prompt leaves the state of its
last real position behind. Everything in float32. The skip ``D x`` and the
gate are the caller's.

**A decode step** (:func:`ssd_update`) is one Pallas call a layer. The
state is 4 MB a sequence a layer at Falcon-H1-34B's sizes (32 x 256 x 128
float32), more than the layer's int8 weights at 64 sequences: the step is
this state read once and written once, and nothing else may copy it. The
kernel reads layer ``layer``'s state where it lies in the stack the layer
scan carries, ``(L, B, H, N, P)`` (the layer index rides scalar prefetch,
the result is aliased onto the input, as :mod:`.decode_attention` and the
stacked ``int8_matmul`` read theirs: a plain ``state[layer]`` under
``lax.scan`` is a copy of the layer's slice out and in), applies decay and
the rank-one update, reads ``y`` out against ``C`` in the same pass and
writes each tile once. **A slot that holds no live sequence costs no state
traffic**: its depth is the window (``park_cache_index``), its index map
repeats the block the pipeline already holds and the kernel does not touch
it. ``B`` and ``C`` come in as ``(B, G, N, 1)``, so that a group's vector is
a column that spreads over the lanes without a transpose in the kernel
(:mod:`.selective_scan`'s form).

**A prefill** (:func:`ssd_chunked`) is the chunked form: inside a chunk of
``Q`` positions the recurrence is two matrix products against a decay
matrix ``L[i, j] = exp(sum_{j < k <= i} dt_k A)``, a chunk leaves one
state, and the chunks' states combine through the same kind of matrix over
chunks: einsums on the matrix unit, no loop over positions (as
``lax.scan`` a position it is several device operations a position a
layer, and a traced window's profiler then outlasts the benchmark's drain:
PERF.md section 6, PR 34). :func:`ssd_recurrence` is the recurrence a
position: the numerics reference of both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST

_TILE_BYTES = 1 << 20  # a grid step's state tile: in and out, two buffers each


def ssd_step(state, decay, dtx, b, c):
    """One position a row, plain ``jax.numpy``. ``state`` (B, H, N, P);
    ``decay`` (B, H) = ``exp(dt A)``; ``dtx`` (B, H, P) = ``dt x``; ``b``,
    ``c`` (B, G, N). Returns ``(new state, y (B, H, P))``."""
    bsz, h, n, p = state.shape
    g = b.shape[1]
    s = state.reshape(bsz, g, h // g, n, p)
    new = (
        decay.reshape(bsz, g, h // g, 1, 1) * s
        + b[:, :, None, :, None] * dtx.reshape(bsz, g, h // g, 1, p)
    )
    y = jnp.sum(new * c[:, :, None, :, None], axis=3)
    return new.reshape(state.shape), y.reshape(bsz, h, p)


def ssd_recurrence(x, dt, a, b, c, state=None):
    """Whole sequences a position at a time (``lax.scan`` over
    :func:`ssd_step`). ``x`` (B, S, H, P); ``dt`` (B, S, H); ``a`` (H,);
    ``b``, ``c`` (B, S, G, N). Returns ``(y (B, S, H, P), the state after
    the last position (B, H, N, P))``."""
    bsz, _, h, p = x.shape
    if state is None:
        state = jnp.zeros((bsz, h, b.shape[-1], p), jnp.float32)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        return ssd_step(s, jnp.exp(dt_t * a), dt_t[..., None] * x_t, b_t, c_t)

    seq = lambda t: jnp.swapaxes(t, 0, 1)  # noqa: E731
    state, ys = jax.lax.scan(step, state, (seq(x), seq(dt), seq(b), seq(c)))
    return seq(ys), state


def _decay_matrix(cs):
    """``exp(cs[..., i, :] - cs[..., j, :])`` for ``j <= i``, else 0, from
    the inclusive running sums ``cs`` (..., Q, H) of ``dt A`` (<= 0): what
    position ``j``'s contribution has decayed by at position ``i``. The
    mask comes before the exponential: above the diagonal the difference is
    positive and may overflow."""
    q = cs.shape[-2]
    diff = cs[..., :, None, :] - cs[..., None, :, :]  # (..., Qi, Qj, H)
    keep = jnp.tril(jnp.ones((q, q), jnp.bool_))[:, :, None]
    return jnp.where(keep, jnp.exp(jnp.where(keep, diff, 0.0)), 0.0)


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """Whole sequences from a zero state in chunks of ``chunk`` positions.
    Shapes and result as :func:`ssd_recurrence`. ``S`` is padded to whole
    chunks with ``dt = 0`` (the identity on the state)."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    pad = (-s) % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c)
        )
    nc = (s + pad) // chunk
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    dtr = f32(dt).reshape(bsz, nc, chunk, h)
    dtx = (f32(x) * f32(dt)[..., None]).reshape(bsz, nc, chunk, g, r, p)
    br = f32(b).reshape(bsz, nc, chunk, g, n)
    cr = f32(c).reshape(bsz, nc, chunk, g, n)
    cs = jnp.cumsum(dtr * a, axis=2)  # (B, nc, Q, H), inclusive

    ein = lambda spec, *ts: jnp.einsum(spec, *ts, precision=HIGHEST)  # noqa: E731
    # inside a chunk: y_i += sum_{j <= i} (C_i . B_j) L[i, j] dt_j x_j
    lm = _decay_matrix(cs).reshape(bsz, nc, chunk, chunk, g, r)
    scores = ein("bcign,bcjgn->bcijg", cr, br)
    y = ein("bcijgr,bcjgrp->bcigrp", scores[..., None] * lm, dtx)
    # what a chunk leaves: sum_j exp(cs_last - cs_j) B_j (dt_j x_j)^T
    to_end = jnp.exp(cs[:, :, -1:, :] - cs).reshape(bsz, nc, chunk, g, r)
    left = ein("bcjgn,bcjgrp->bcgrnp", br, dtx * to_end[..., None])
    # over chunks: the state after chunk k is sum_{m <= k} M[k, m] left_m
    total = jnp.cumsum(cs[:, :, -1, :], axis=1)  # (B, nc, H): through chunk k
    over = _decay_matrix(total).reshape(bsz, nc, nc, g, r)
    after = ein("bkmgr,bmgrnp->bkgrnp", over, left)
    before = jnp.pad(after[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * 4)
    carried = ein("bcign,bcgrnp->bcigrp", cr, before)
    y = y + carried * jnp.exp(cs).reshape(bsz, nc, chunk, g, r, 1)
    y = y.reshape(bsz, s + pad, h, p)
    return (y[:, :s] if pad else y), after[:, -1].reshape(bsz, h, n, p)


def ssd_heads_block(heads_a_group: int, n: int, p: int) -> int | None:
    """Heads of one group a grid step of :func:`ssd_update` takes: the
    largest divisor of a group's heads whose state tile is at most 1 MB
    (8 heads of 256 x 128 float32). None where the kernel takes no such
    state: ``P`` no whole number of 128-lane tiles, ``N`` of 8-row tiles."""
    if p % 128 or n % 8:
        return None
    fit = max(1, _TILE_BYTES // (4 * n * p))
    return max(d for d in range(1, heads_a_group + 1)
               if heads_a_group % d == 0 and d <= fit)


def _held_blocks(live, n_blocks: int):
    """Per slot, the (slot, head block) a dead slot's index maps repeat:
    the last block of the live slot before it (the first block of the live
    slot after it where none is before; (0, 0) where no slot is live),
    which the pipeline holds already."""
    bsz = live.shape[0]
    idx = jnp.arange(bsz, dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(live, idx, -1))
    nxt = jax.lax.cummin(jnp.where(live, idx, bsz), reverse=True)
    src = jnp.where(prev >= 0, prev, jnp.where(nxt < bsz, nxt, 0))
    blk = jnp.where(prev >= 0, n_blocks - 1, 0)
    return src.astype(jnp.int32), blk.astype(jnp.int32)


def ssd_update(state_stack, layer, decay, dtx, b, c, pos, window: int, *,
               interpret: bool | None = None):
    """One decode step of layer ``layer``, its state updated in place.

    ``state_stack`` (L, B, H, N, P) float32; ``layer`` int32 scalar
    (traced); ``decay`` (B, H); ``dtx`` (B, H, P); ``b``, ``c`` (B, G, N);
    ``pos`` (B,) int32: ``pos[b] >= window`` is a slot with nothing in it,
    whose state is neither read nor written and whose ``y`` is zeros.
    Returns ``(y (B, H, P), the stack)``; the stack is the input's buffer
    (``input_output_aliases``), of which only layer ``layer``'s live slots'
    tiles are touched."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_layers, bsz, h, n, p = state_stack.shape
    g = b.shape[1]
    hb = ssd_heads_block(h // g, n, p)
    assert hb, (state_stack.shape, g)
    n_blocks = h // hb
    a_group = (h // g) // hb  # head blocks a group
    pos = pos.astype(jnp.int32)
    src, blk = _held_blocks(pos < window, n_blocks)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def kernel(layer_ref, pos_ref, src_ref, blk_ref,
               decay_ref, dtx_ref, b_ref, c_ref, s_ref, y_ref, out_ref):
        del layer_ref, src_ref, blk_ref
        live = pos_ref[pl.program_id(0)] < window

        @pl.when(live)
        def _step():
            # the group's B and C: (N, 1) columns, spread over the lanes
            b_full = jnp.broadcast_to(b_ref[0, 0], (n, p))
            c_full = jnp.broadcast_to(c_ref[0, 0], (n, p))
            for i in range(hb):
                new = (
                    s_ref[0, 0, i] * decay_ref[0, i : i + 1, :]
                    + b_full * dtx_ref[0, i : i + 1, :]
                )
                out_ref[0, 0, i] = new
                y_ref[0, i : i + 1, :] = jnp.sum(
                    new * c_full, axis=0, keepdims=True
                )

        @pl.when(jnp.logical_not(live))
        def _dead():
            y_ref[:] = jnp.zeros_like(y_ref)

    def state_map(bb, j, layer_ref, pos_ref, src_ref, blk_ref):
        live = pos_ref[bb] < window
        return (
            layer_ref[0], jnp.where(live, bb, src_ref[bb]),
            jnp.where(live, j, blk_ref[bb]), 0, 0,
        )

    row_spec = pl.BlockSpec((1, hb, p), lambda bb, j, *_: (bb, j, 0))
    col_spec = pl.BlockSpec(
        (1, 1, n, 1), lambda bb, j, *_: (bb, j // a_group, 0, 0)
    )
    state_spec = pl.BlockSpec((1, 1, hb, n, p), state_map)
    y, stack = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(bsz, n_blocks),
            in_specs=[row_spec, row_spec, col_spec, col_spec, state_spec],
            out_specs=[row_spec, state_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, p), jnp.float32),
            jax.ShapeDtypeStruct(state_stack.shape, jnp.float32),
        ],
        # operand 8 (after the four prefetched scalars and decay, dtx, b,
        # c) is the stack; result 1 is the same buffer
        input_output_aliases={8: 1},
        name="ssd_update",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), pos, src, blk,
        jnp.broadcast_to(f32(decay)[..., None], (bsz, h, p)), f32(dtx),
        f32(b)[..., None], f32(c)[..., None], state_stack,
    )
    return y, stack

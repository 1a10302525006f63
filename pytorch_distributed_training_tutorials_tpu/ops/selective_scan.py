"""The selective scan of a Mamba-1 layer.

``s_t = exp(delta_t A) s_{t-1} + (delta_t u_t) B_t^T`` and ``y_t = s_t C_t``
with ``A`` (N, E), a state ``s`` (N, E) a sequence (``E`` on the lanes), and
``u``, ``delta`` (E,), ``B``, ``C`` (N,) a position. ``delta_t = 0`` is the
identity on the state, to the last bit (``exp(0) = 1`` and ``0 * u = 0``):
that is how a right-padded prompt leaves the state of its last real
position behind. Everything in float32. The skip ``D u`` and the gate are
the caller's.

:func:`selective_step` is one position a row (a decode step: plain
``jax.numpy``, the compiler fuses it into the state's write).
:func:`selective_scan` is a whole sequence (a prefill). Written as
``lax.scan`` over :func:`selective_step` (:func:`selective_scan_reference`)
it is some six small device operations a position a layer, 110,000 for one
prompt of 2,048 through nine layers: cheap in time on the chip, but every
one an event of a traced window, and the profiler's stop then outlasts the
benchmark's drain (PERF.md section 6, PR 34). So a sequence runs as one
Pallas call a layer: the grid walks (row, block of channels, block of
positions), the state of a block of channels stays in fast memory across
the blocks of positions, and inside a block the positions are a loop.
``B_t`` and ``C_t`` come in as ``(S, N, 1)`` so that a position's vector is
a column that spreads over the lanes without a transpose in the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 8  # positions a loop iteration: one sublane tile of u, delta and y


def selective_step(s, u, delta, a, b, c):
    """One position a row. ``s`` (B, N, E); ``u``, ``delta`` (B, E); ``a``
    (N, E); ``b``, ``c`` (B, N). Returns ``(s_new, y (B, E))``."""
    s = jnp.exp(delta[:, None, :] * a) * s + (
        (delta * u)[:, None, :] * b[:, :, None]
    )
    return s, jnp.sum(s * c[:, :, None], axis=1)


def selective_scan_reference(u, delta, a, b, c, s0=None):
    """Whole sequences as ``lax.scan`` over :func:`selective_step`: the
    kernel's numerics reference, and the path of a width the kernel does
    not take."""
    bsz, _, e = u.shape
    if s0 is None:
        s0 = jnp.zeros((bsz, a.shape[0], e), jnp.float32)

    def step(s, inp):
        return selective_step(s, *inp[:2], a, *inp[2:])

    seq = lambda t: jnp.swapaxes(t, 0, 1)  # noqa: E731
    s, ys = jax.lax.scan(step, s0, (seq(u), seq(delta), seq(b), seq(c)))
    return seq(ys), s


def _scan_kernel(u_ref, d_ref, a_ref, b_ref, c_ref, y_ref, s_ref, state, *,
                 block_t: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        state[:] = jnp.zeros_like(state)

    a = a_ref[:]  # (N, be)

    def rows(g, s):
        base = pl.multiple_of(g * _ROWS, _ROWS)
        u8 = u_ref[0, pl.ds(base, _ROWS), :]
        d8 = d_ref[0, pl.ds(base, _ROWS), :]
        for r in range(_ROWS):
            d = d8[r : r + 1]  # (1, be)
            # a position's B and C: (N, 1) columns, spread over the lanes
            s = jnp.exp(d * a) * s + (d * u8[r : r + 1]) * b_ref[0, base + r]
            y_ref[0, pl.ds(base + r, 1), :] = jnp.sum(
                s * c_ref[0, base + r], axis=0, keepdims=True
            )
        return s

    state[:] = jax.lax.fori_loop(0, block_t // _ROWS, rows, state[:])

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        s_ref[0] = state[:]


def selective_scan(u, delta, a, b, c, *, block_e: int = 512,
                   block_t: int = 128, interpret: bool | None = None):
    """Whole sequences from a zero state. ``u``, ``delta`` (B, S, E);
    ``a`` (N, E); ``b``, ``c`` (B, S, N). Returns ``(y (B, S, E), the state
    after the last position (B, N, E))``. ``S`` is padded to whole blocks
    with ``delta = 0`` (the identity on the state); an ``E`` that is no
    whole number of 128-lane tiles takes :func:`selective_scan_reference`.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bsz, s, e = u.shape
    n = a.shape[0]
    if e % 128:
        return selective_scan_reference(u, delta, a, b, c)
    block_e = next(w for w in (block_e, 256, 128) if e % w == 0)
    block_t = min(block_t, -(-s // _ROWS) * _ROWS)
    pad = (-s) % block_t
    if pad:
        u, delta, b, c = (
            jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (u, delta, b, c)
        )
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    seq_spec = pl.BlockSpec((1, block_t, block_e), lambda r, i, j: (r, j, i))
    col_spec = pl.BlockSpec((1, block_t, n, 1), lambda r, i, j: (r, j, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, block_t=block_t),
        grid=(bsz, e // block_e, (s + pad) // block_t),
        in_specs=[
            seq_spec, seq_spec,
            pl.BlockSpec((n, block_e), lambda r, i, j: (0, i)),
            col_spec, col_spec,
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, n, block_e), lambda r, i, j: (r, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s + pad, e), jnp.float32),
            jax.ShapeDtypeStruct((bsz, n, e), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, block_e), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="selective_scan",
        interpret=interpret,
    )(f32(u), f32(delta), f32(a), f32(b)[..., None], f32(c)[..., None])
    return (y[:, :s] if pad else y), state

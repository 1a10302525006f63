"""Pallas blockwise softmax cross entropy: the LM loss without the logits.

The reference computes its loss as ``F.cross_entropy(output, targets)`` over
fully materialized logits (reference ``ddp_gpus.py:37``); the TPU twin did
the same with ``optax.softmax_cross_entropy_with_integer_labels`` over the
``(B, S, V)`` lm_head output. At LM scale that tensor is the single largest
activation of the train step (350m config, B=8, S=2048: 2 GiB of bf16
logits plus the float32 softmax temps behind it) and every byte of it is
memory-bound tail work. This module removes it with the same online-softmax
decomposition :mod:`.flash_attention` uses for the (S, S) score matrix:

- forward: one MXU pass per (row-block, vocab-block) tile of the lm_head
  matmul, folding each logits tile into a running (max, sum-exp, target
  logit) state in VMEM scratch — the ``(N, V)`` logits only ever exist as a
  ``(block_n, block_v)`` tile. Residual: the O(N) per-token logsumexp.
- backward (``jax.custom_vjp``): two kernels re-derive logits tiles
  blockwise from the saved logsumexp and fuse softmax-minus-one-hot into
  the gradient matmuls directly — ``dh`` accumulates over vocab blocks,
  ``dW`` over row blocks (the dq/dkv split of the flash backward).
- numerics: logits/softmax in float32 regardless of input dtype; matmul
  operands stay in the input dtype with f32 accumulation
  (``preferred_element_type``), matching the repo kernel template.

``interpret=None`` auto-selects Pallas interpreter mode off-TPU (the
:func:`.flash_attention.flash_attention` pattern) so the identical kernel
code path runs on the forced 8-device CPU test mesh, where it lowers to
plain HLO and composes with GSPMD sharding. On real multi-chip meshes a
``pallas_call`` is a single-device program; the tensor-parallel vocab-split
head (``TP_RULES``' ``lm_head: P(None, 'model')``) goes through
:func:`fused_cross_entropy_tp`, which states the Megatron layout in
``shard_map``: each shard runs the same kernels over its vocab columns with
locally shifted targets, then an axis-reduced logsumexp + psum of the
target logit stitch the global loss.

Equivalence with the optax path is pinned by ``tests/test_fused_loss.py``;
the ``compiled.memory_analysis()``/HLO receipt that no ``(B, S, V)`` float
intermediate survives compilation lives there too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_tutorials_tpu.utils.compat import (
    shard_map_nocheck,
)

NEG_INF = float("-inf")  # plain float: no jax arrays at import time

# Defaults sized for LM-head shapes (D ~ 1-4k, V ~ 32-256k): the VMEM
# working set per call is the double-buffered block_n*D (rows) and
# D*block_v (weights) blocks, the block_n*block_v f32 logits tile and the
# row scratch — 8 MiB forward at D=2048 in bf16; the backward calls hold
# more and shrink their blocks to fit (_fit_vmem).
# block_n also sets the head-weight re-read factor (each row block streams
# the whole W): HBM traffic for W is ceil(N / block_n) * |W|, so prefer
# the largest block_n whose tiles still fit VMEM when tuning on-chip.
DEFAULT_BLOCK_N = 512
DEFAULT_BLOCK_V = 512

# What the pipelined blocks plus the f32 accumulator of one call may hold.
# Mosaic's scoped-VMEM limit on a v5e core is 16 MiB; the 4 MiB left over
# is for the (block_n, block_v) f32 logits/dS tiles and the row vectors.
_VMEM_BLOCK_BUDGET = 12 * 1024 * 1024


def _clamp_block(b: int, dim: int, interpret: bool) -> int:
    """Clamp a block size to the (8-aligned) dim. On real TPU Mosaic wants
    lane dims in 128-multiples OR spanning the whole array, so sub-128
    user blocks round up (the lse/loss row tiles put block_n in lanes;
    the logits tile puts block_v there). Interpreter mode has no tiling
    constraint — tests keep small blocks to exercise multi-block layouts
    on small problems (the :func:`.flash_attention._block_sizes` rule)."""
    d8 = -(-max(8, dim) // 8) * 8
    if not interpret:
        b = -(-b // 128) * 128
    return d8 if b >= d8 else b


def _fit_vmem(bn: int, bv: int, d: int, itemsize: int, acc: str | None):
    """Shrink (block_n, block_v) until one call's VMEM working set fits
    :data:`_VMEM_BLOCK_BUDGET` (real-TPU path only — the interpreter has
    no VMEM). Every call double-buffers a (bn, d) hidden block and a
    (d, bv) head block; the backward calls add a double-buffered output
    block and an f32 accumulator of the same shape on one side —
    ``acc="n"`` for dh (rows), ``acc="v"`` for dW (vocab). At 512/512,
    bf16 and d = 2048 that is 8 MiB forward but 16.8 MiB backward, so dh
    runs 256-row blocks and dW 256-column blocks there; d <= 1024 keeps
    the defaults. The accumulator side halves first (it weighs 3x), and a
    block only halves while it stays a 128-multiple (the lane rule of
    :func:`_clamp_block`)."""
    per_n = d * (2 * itemsize + (2 * itemsize + 4 if acc == "n" else 0))
    per_v = d * (2 * itemsize + (2 * itemsize + 4 if acc == "v" else 0))
    while bn * per_n + bv * per_v > _VMEM_BLOCK_BUDGET:
        if bn % 256 == 0 and (acc != "v" or bv % 256):
            bn //= 2
        elif bv % 256 == 0:
            bv //= 2
        else:
            break
    return bn, bv


def _row8(vec, total):
    """Pad a per-row (N,) vector to ``total`` and broadcast over the 8
    sublanes — Mosaic requires (8, 128)-alignable tiles, so a bare
    (1, block) row is not expressible (the flash lse layout)."""
    padded = jnp.pad(vec, (0, total - vec.shape[0]))
    return jnp.broadcast_to(padded[None, :], (8, total))


def _fwd_kernel(
    h_ref, w_ref, y_ref, lse_ref, tgt_ref, m_ref, l_ref, t_ref,
    *, block_n: int, block_v: int, n_v: int, vocab: int,
):
    """One (row-block, vocab-block) tile of the online-logsumexp forward."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        t_ref[:] = jnp.zeros_like(t_ref)

    # operands stay in the input dtype, accumulation f32 (house rule —
    # see the _fwd_kernel note in flash_attention)
    s = jax.lax.dot_general(
        h_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (BN, BV) f32 — the only form the logits ever take
    col = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1
    )
    # zero-padded vocab tail columns must not score
    s = jnp.where(col < vocab, s, NEG_INF)

    m_prev = m_ref[:, :1]  # (BN, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    # a block whose every column is padded keeps m == -inf; exp(-inf - -inf)
    # would be NaN — guard the shift (those columns contribute 0)
    shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(s - shift)  # (BN, BV)
    corr = jnp.exp(m_prev - shift)  # (BN, 1); exp(-inf - 0) = 0 at init
    l_ref[:, :1] = l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
    m_ref[:, :1] = m_new
    # target logit: exactly one (row, col) hit across all vocab blocks —
    # out-of-range targets (padded rows; other shards' tokens in the TP
    # variant) hit nothing and contribute 0. The col < vocab guard keeps a
    # shifted target that lands in the padded tail (TP variant, V_local
    # not a block multiple) off the -inf padding columns.
    y = y_ref[0, :]  # (BN,) int32
    hit = (col == y[:, None]) & (col < vocab)
    t_ref[:, :1] += jnp.where(hit, s, 0.0).sum(axis=-1, keepdims=True)

    @pl.when(j == n_v - 1)
    def _flush():
        l = l_ref[:, :1]
        # real vocab >= 1 column per row => l > 0; all-padded rows only
        # exist for row-padding tails (sliced away by the wrapper)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        m = m_ref[:, :1]
        lse = jnp.where(m == NEG_INF, NEG_INF, m + jnp.log(safe_l))
        lse_ref[:] = jnp.broadcast_to(lse[:, 0][None, :], lse_ref.shape)
        tgt_ref[:] = jnp.broadcast_to(
            t_ref[:, 0][None, :], tgt_ref.shape
        )


def _softmax_minus_onehot(s, y_row, g_row, lse_row, col, vocab):
    """The shared dS tile of both backward kernels:
    ``g * (softmax(s) - onehot(y))`` recomputed from the saved logsumexp."""
    s = jnp.where(col < vocab, s, NEG_INF)
    lse = lse_row[:, None]  # (BN, 1)
    # padded rows carry lse == 0 with g == 0 — the g factor zeroes them
    p = jnp.exp(s - jnp.where(lse == NEG_INF, 0.0, lse))
    # col < vocab: see the forward's target-hit guard (padded-tail columns
    # must stay gradient-free even when a shifted target lands on them)
    hit = (col == y_row[:, None]) & (col < vocab)
    return (p - hit.astype(jnp.float32)) * g_row[:, None]


def _dh_kernel(
    h_ref, w_ref, y_ref, lse_ref, g_ref, dh_ref, acc_ref,
    *, block_n: int, block_v: int, n_v: int, vocab: int,
):
    """dh = sum_v dS @ W^T, accumulated over vocab blocks."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s = jax.lax.dot_general(
        h_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    col = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1
    )
    ds = _softmax_minus_onehot(
        s, y_ref[0, :], g_ref[0, :], lse_ref[0, :], col, vocab
    )
    acc_ref[:] += jax.lax.dot_general(
        ds.astype(w_ref.dtype), w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == n_v - 1)
    def _flush():
        dh_ref[:] = acc_ref[:].astype(dh_ref.dtype)


def _dw_kernel(
    h_ref, w_ref, y_ref, lse_ref, g_ref, dw_ref, acc_ref,
    *, block_n: int, block_v: int, n_n: int, vocab: int,
):
    """dW = sum_rows H^T @ dS for one vocab block, accumulated over row
    blocks (the transposed-grid half, like the flash dk/dv kernel)."""
    vj, ri = pl.program_id(0), pl.program_id(1)

    @pl.when(ri == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s = jax.lax.dot_general(
        h_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    col = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1
    )
    ds = _softmax_minus_onehot(
        s, y_ref[0, :], g_ref[0, :], lse_ref[0, :], col, vocab
    )
    acc_ref[:] += jax.lax.dot_general(
        h_ref[:], ds.astype(h_ref.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ri == n_n - 1)
    def _flush():
        dw_ref[:] = acc_ref[:].astype(dw_ref.dtype)


def _pad_inputs(h2, w, y, block_n, block_v, interpret, acc=None):
    """Shared padding/blocking for the forward and backward calls; ``acc``
    names the side a backward call accumulates on (:func:`_fit_vmem`)."""
    n, d = h2.shape
    v = w.shape[1]
    bn = _clamp_block(block_n, n, interpret)
    bv = _clamp_block(block_v, v, interpret)
    if not interpret:
        bn, bv = _fit_vmem(bn, bv, d, h2.dtype.itemsize, acc)
    pad_n = -n % bn
    pad_v = -v % bv
    hf = jnp.pad(h2, ((0, pad_n), (0, 0))) if pad_n else h2
    wf = jnp.pad(w, ((0, 0), (0, pad_v))) if pad_v else w
    # padded rows carry target 0 — their loss/grad rows are sliced away,
    # and in the backward their cotangent is zero-padded
    y8 = _row8(y.astype(jnp.int32), n + pad_n)
    return hf, wf, y8, bn, bv, n + pad_n, v + pad_v


def _fwd_impl(h2, w, y, block_n, block_v, interpret):
    """(lse, target_logit) per row, both (N,) f32 — the logits-free pass."""
    n, d = h2.shape
    v = w.shape[1]
    hf, wf, y8, bn, bv, np_, vp = _pad_inputs(
        h2, w, y, block_n, block_v, interpret
    )
    n_n, n_v = np_ // bn, vp // bv
    hspec = pl.BlockSpec(
        (bn, d), lambda i, j: (i, 0), memory_space=pltpu.VMEM
    )
    wspec = pl.BlockSpec(
        (d, bv), lambda i, j: (0, j), memory_space=pltpu.VMEM
    )
    rowspec = pl.BlockSpec(
        (8, bn), lambda i, j: (0, i), memory_space=pltpu.VMEM
    )
    lse, tgt = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_n=bn, block_v=bv, n_v=n_v, vocab=v
        ),
        grid=(n_n, n_v),
        in_specs=[hspec, wspec, rowspec],
        out_specs=[rowspec, rowspec],
        out_shape=[jax.ShapeDtypeStruct((8, np_), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((bn, 128), jnp.float32)] * 3,
        name="fused_loss_fwd",
        interpret=interpret,
    )(hf, wf, y8)
    return lse[0, :n], tgt[0, :n]


def _bwd_impl(h2, w, y, lse, g, block_n, block_v, interpret):
    """(dh, dW) via blockwise softmax recompute from the saved ``lse``.
    The two calls block independently: each shrinks the side it
    accumulates on to fit VMEM (:func:`_fit_vmem`)."""
    n, d = h2.shape
    v = w.shape[1]
    gf = g.astype(jnp.float32)

    hf, wf, y8, bn, bv, np_, vp = _pad_inputs(
        h2, w, y, block_n, block_v, interpret, acc="n"
    )
    hspec = pl.BlockSpec(
        (bn, d), lambda i, j: (i, 0), memory_space=pltpu.VMEM
    )
    wspec = pl.BlockSpec(
        (d, bv), lambda i, j: (0, j), memory_space=pltpu.VMEM
    )
    rowspec = pl.BlockSpec(
        (8, bn), lambda i, j: (0, i), memory_space=pltpu.VMEM
    )
    dh = pl.pallas_call(
        functools.partial(
            _dh_kernel, block_n=bn, block_v=bv, n_v=vp // bv, vocab=v
        ),
        grid=(np_ // bn, vp // bv),
        in_specs=[hspec, wspec, rowspec, rowspec, rowspec],
        out_specs=hspec,
        out_shape=jax.ShapeDtypeStruct((np_, d), hf.dtype),
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32)],
        name="fused_loss_dh",
        interpret=interpret,
    )(hf, wf, y8, _row8(lse, np_), _row8(gf, np_))

    # transposed grid: outer over vocab blocks, inner accumulates rows
    hf, wf, y8, bn, bv, np_, vp = _pad_inputs(
        h2, w, y, block_n, block_v, interpret, acc="v"
    )
    hspec_t = pl.BlockSpec(
        (bn, d), lambda vj, ri: (ri, 0), memory_space=pltpu.VMEM
    )
    wspec_t = pl.BlockSpec(
        (d, bv), lambda vj, ri: (0, vj), memory_space=pltpu.VMEM
    )
    rowspec_t = pl.BlockSpec(
        (8, bn), lambda vj, ri: (0, ri), memory_space=pltpu.VMEM
    )
    dw = pl.pallas_call(
        functools.partial(
            _dw_kernel, block_n=bn, block_v=bv, n_n=np_ // bn, vocab=v
        ),
        grid=(vp // bv, np_ // bn),
        in_specs=[hspec_t, wspec_t, rowspec_t, rowspec_t, rowspec_t],
        out_specs=wspec_t,
        out_shape=jax.ShapeDtypeStruct((d, vp), wf.dtype),
        scratch_shapes=[pltpu.VMEM((d, bv), jnp.float32)],
        name="fused_loss_dw",
        interpret=interpret,
    )(hf, wf, y8, _row8(lse, np_), _row8(gf, np_))

    return dh[:n], dw[:, :v]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ce(h2, w, y, block_n, block_v, interpret):
    lse, tgt = _fwd_impl(h2, w, y, block_n, block_v, interpret)
    return lse - tgt


def _fused_ce_fwd(h2, w, y, block_n, block_v, interpret):
    lse, tgt = _fwd_impl(h2, w, y, block_n, block_v, interpret)
    return lse - tgt, (h2, w, y, lse)


def _fused_ce_bwd(block_n, block_v, interpret, res, g):
    h2, w, y, lse = res
    dh, dw = _bwd_impl(h2, w, y, lse, g, block_n, block_v, interpret)
    # integer targets take a float0 cotangent (jax's tangent type for
    # non-differentiable inputs)
    return dh, dw, np.zeros(y.shape, jax.dtypes.float0)


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_cross_entropy(
    hidden: jax.Array,
    lm_head: jax.Array,
    targets: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    block_v: int = DEFAULT_BLOCK_V,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-token softmax cross entropy of ``hidden @ lm_head`` against
    integer ``targets``, logits-free.

    ``hidden``: (..., D) final hidden states; ``lm_head``: (D, V) head
    kernel; ``targets``: (...) int, same leading shape as ``hidden``.
    Returns per-token losses of ``targets.shape`` in float32 — the same
    contract as ``optax.softmax_cross_entropy_with_integer_labels(
    hidden @ lm_head, targets)`` (reference loss ``ddp_gpus.py:37``), so
    row-validity masks (``ShardedLoader.valid_mask``) weight it the same
    way. Peak temp is O(block_n * block_v) VMEM per core plus the O(N)
    logsumexp residual; the (..., V) logits never exist in HBM.

    ``interpret=None`` auto-selects interpreter mode off-TPU so the same
    code path tests on the CPU mesh.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d = hidden.shape[-1]
    if hidden.shape[:-1] != targets.shape:
        raise ValueError(
            f"hidden {hidden.shape} / targets {targets.shape} mismatch: "
            "hidden must be targets.shape + (d_model,)"
        )
    h2 = hidden.reshape(-1, d)
    y = targets.reshape(-1)
    loss = _fused_ce(h2, lm_head, y, block_n, block_v, interpret)
    return loss.reshape(targets.shape)


def fused_cross_entropy_reference(
    hidden: jax.Array, lm_head: jax.Array, targets: jax.Array
) -> jax.Array:
    """Materialized-logits statement of the same math (tests/off-TPU): the
    f32-accumulated lm_head matmul followed by the standard logsumexp CE."""
    logits = jnp.einsum(
        "...d,dv->...v", hidden, lm_head,
        preferred_element_type=jnp.float32,
    )
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(
        logits, targets[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    return lse - tgt


# -- tensor-parallel vocab-split head (shard_map) ---------------------------


def _row_axis(mesh, data_axis, n):
    """Shard loss rows over the data axis only when they divide it (the
    int8_matmul_tp rule) — replicated rows are correct, just unsharded."""
    if data_axis in mesh.shape and n % mesh.shape[data_axis] == 0:
        return data_axis
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fused_ce_tp(h2, w, y, mesh, axis, data_axis, block_n, block_v,
                 interpret):
    loss, _ = _fused_ce_tp_fwd(
        h2, w, y, mesh, axis, data_axis, block_n, block_v, interpret
    )
    return loss


def _fused_ce_tp_fwd(h2, w, y, mesh, axis, data_axis, block_n, block_v,
                     interpret):
    n = h2.shape[0]
    row = _row_axis(mesh, data_axis, n)

    def fwd_local(hl, wl, yl):
        v_local = wl.shape[1]
        # this shard owns global columns [off, off + v_local): shift the
        # targets into local coordinates — out-of-shard targets go out of
        # range and the kernel's one-hot hits nothing (contribution 0)
        off = jax.lax.axis_index(axis) * v_local
        lse_l, tgt_l = _fwd_impl(
            hl, wl, yl - off, block_n, block_v, interpret
        )
        # axis-reduced logsumexp over the vocab shards: shift by the
        # cross-shard max so the exp cannot overflow
        m = jax.lax.pmax(lse_l, axis)
        lse_g = m + jnp.log(jax.lax.psum(jnp.exp(lse_l - m), axis))
        # exactly one shard holds the target column
        tgt_g = jax.lax.psum(tgt_l, axis)
        return lse_g, tgt_g

    lse, tgt = shard_map_nocheck(
        fwd_local,
        mesh=mesh,
        in_specs=(P(row, None), P(None, axis), P(row)),
        out_specs=(P(row), P(row)),
    )(h2, w, y)
    return lse - tgt, (h2, w, y, lse)


def _fused_ce_tp_bwd(mesh, axis, data_axis, block_n, block_v, interpret,
                     res, g):
    h2, w, y, lse = res
    n = h2.shape[0]
    row = _row_axis(mesh, data_axis, n)

    def bwd_local(hl, wl, yl, lsel, gl):
        v_local = wl.shape[1]
        off = jax.lax.axis_index(axis) * v_local
        # the global lse makes each shard's recomputed tile the GLOBAL
        # softmax restricted to its columns, so the two partials compose:
        # dh sums over vocab shards (psum), dW is per-shard-exact
        dh_l, dw_l = _bwd_impl(
            hl, wl, yl - off, lsel, gl, block_n, block_v, interpret
        )
        dh_g = jax.lax.psum(dh_l, axis)
        if row is not None:
            # w is replicated over the data axis: its gradient sums the
            # row shards (the allreduce GSPMD would have inserted)
            dw_l = jax.lax.psum(dw_l, data_axis)
        return dh_g, dw_l

    dh, dw = shard_map_nocheck(
        bwd_local,
        mesh=mesh,
        in_specs=(P(row, None), P(None, axis), P(row), P(row), P(row)),
        out_specs=(P(row, None), P(None, axis)),
    )(h2, w, y, lse, g)
    return dh, dw, np.zeros(y.shape, jax.dtypes.float0)


_fused_ce_tp.defvjp(_fused_ce_tp_fwd, _fused_ce_tp_bwd)


def fused_cross_entropy_tp(
    hidden: jax.Array,
    lm_head: jax.Array,
    targets: jax.Array,
    mesh,
    *,
    axis: str = "model",
    data_axis: str = "data",
    block_n: int = DEFAULT_BLOCK_N,
    block_v: int = DEFAULT_BLOCK_V,
    interpret: bool | None = None,
) -> jax.Array:
    """:func:`fused_cross_entropy` for a tensor-parallel vocab-split head
    (``TP_RULES``' ``lm_head/kernel: P(None, 'model')``), stated in
    ``shard_map`` because a ``pallas_call`` is a single-device program
    GSPMD cannot partition (the :func:`..ops.quant.int8_matmul_tp` rule).

    Each shard streams its own vocab columns through the same kernels with
    locally shifted targets; an axis-reduced logsumexp
    (``pmax`` + ``log(psum(exp))``) and a psum of the per-shard target
    logit assemble the exact global loss — numerics match the unsharded
    op to float tolerance. Rows shard over ``data_axis`` when they divide
    it. Requires V divisible by the ``axis`` size (the TP head layout
    already does).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no {axis!r} axis: {dict(mesh.shape)}")
    v = lm_head.shape[1]
    if v % mesh.shape[axis]:
        raise ValueError(
            f"vocab ({v}) not divisible by the {axis!r} axis "
            f"({mesh.shape[axis]})"
        )
    d = hidden.shape[-1]
    if hidden.shape[:-1] != targets.shape:
        raise ValueError(
            f"hidden {hidden.shape} / targets {targets.shape} mismatch: "
            "hidden must be targets.shape + (d_model,)"
        )
    h2 = hidden.reshape(-1, d)
    y = targets.reshape(-1).astype(jnp.int32)
    loss = _fused_ce_tp(
        h2, lm_head, y, mesh, axis, data_axis, block_n, block_v, interpret
    )
    return loss.reshape(targets.shape)

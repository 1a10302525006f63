"""Pallas blockwise flash attention: causal softmax attention without the
(S, S) score matrix.

The reference has no attention kernel at all (its only transformer is the
vendored Llama-7B loaded for the placement demo, never run —
``/root/reference/03.model_parallel.ipynb`` cell 2; SURVEY.md section 5.7).
Dense :func:`..models.transformer.causal_attention` materializes a
``(B, H, S, S)`` float32 score tensor — O(S^2) HBM that caps single-chip
context length. This module is the TPU-native fix: the standard
flash-attention decomposition (online softmax over key blocks) as a Pallas
kernel, so scores only ever exist as a ``(block_q, block_k)`` tile in VMEM.

- forward: one MXU pass per (q-block, k-block) pair with the running
  (m, l, acc) online-softmax state in VMEM scratch, carried across the
  innermost grid dimension (the K-blocked accumulator pattern of
  :func:`..ops.quant.int8_matmul`, this repo's house kernel template).
  Blocks entirely above the causal diagonal are predicated off with
  ``pl.when``.
- backward: custom VJP (the flash recompute strategy — O(S) residuals:
  per-row logsumexp + the output). Two Pallas kernels re-derive score
  tiles blockwise: dq accumulates over key blocks, dk/dv over query blocks.
- numerics: scores/softmax in float32 regardless of input dtype (matching
  ``masked_attention``'s mixed-precision contract); probabilities cast back
  to the value dtype for the MXU context matmul.

``flash_attention`` is a drop-in ``attention_fn`` for
:class:`..models.transformer.TransformerConfig` — same (B, S, H, D)
signature and causal semantics as ``causal_attention``, equivalence-tested
in ``tests/test_flash_attention.py``.

``flash_attention_forward`` is the forward kernel alone, for a serving
prefill (``Attention`` picks it by :func:`prefill_takes_kernel`): K and V
stay at their stored head count — the key and value blocks' index maps take
the query head's group — and nothing is saved for a backward pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")  # plain float: no jax arrays at import time


def _causal_overlap(qi, kk, block_q: int, block_k: int):
    """True when key block ``kk`` has any position <= some query position
    of block ``qi`` (i.e. the block is not entirely above the diagonal)."""
    return kk * block_k <= qi * block_q + block_q - 1


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale: float, block_q: int, block_k: int, n_k: int,
):
    """One (q-block, k-block) tile of the online-softmax forward."""
    qi, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(_causal_overlap(qi, kk, block_q, block_k))
    def _body():
        # matmul operands stay in the INPUT dtype: upcasting bf16->f32
        # adds no information (products accumulate f32 either way via
        # preferred_element_type), and Mosaic is what decides the MXU
        # pass structure — measured identical on v5e with or without the
        # explicit upcast (it folds the convert into the op), so the
        # native form is kept for clarity, not speed. float32 operands it
        # multiplies in ONE bfloat16 pass (v5e, PR 37: bit for bit the
        # result of operands rounded to bfloat16 beforehand) — what XLA's
        # default precision does in the dense form's products
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (BQ, BK) f32
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (BQ, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # rows whose every key is causally masked keep m == -inf; exp(-inf
        # - -inf) would be NaN — guard the shift (those rows contribute 0)
        shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - shift)  # (BQ, BK)
        corr = jnp.exp(m_prev - shift)  # (BQ, 1); exp(-inf-0)=0 at init
        l_ref[:, :1] = l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32,
        )
        m_ref[:, :1] = m_new

    @pl.when(kk == n_k - 1)
    def _flush():
        l = l_ref[:, :1]
        # causal => every in-range row saw its own diagonal, l > 0; fully
        # masked rows only exist for padded sequence tails (sliced away by
        # the wrapper) — emit 0, not NaN, for them
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        m = m_ref[:, :1]
        lse = jnp.where(m == NEG_INF, NEG_INF, m + jnp.log(safe_l))
        # row vectors live as (8, block) tiles: Mosaic requires the last
        # two block dims (8, 128)-aligned, so a bare (1, block) row is not
        # expressible — broadcast over the 8 sublanes instead
        lse_ref[0] = jnp.broadcast_to(lse[:, 0][None, :], lse_ref.shape[1:])


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, scale: float, block_q: int, block_k: int, n_k: int,
):
    """dq = sum_k dS @ K * scale, accumulated over key blocks."""
    qi, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_causal_overlap(qi, kk, block_q, block_k))
    def _body():
        # native-dtype operands, f32 accumulation (see _fwd_kernel note)
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        lse = lse_ref[0, 0, :][:, None]  # (BQ, 1)
        # p = softmax row (exact, via the saved logsumexp); masked rows of a
        # padded tail have lse == -inf -> guard like the forward
        p = jnp.exp(s - jnp.where(lse == NEG_INF, 0.0, lse))
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK) f32
        ds = p * (dp - delta_ref[0, 0, :][:, None])  # (BQ, BK)
        acc_ref[:] += jax.lax.dot(
            ds.astype(k_ref.dtype), k_ref[0],
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(kk == n_k - 1)
    def _flush():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, block_q: int, block_k: int, n_q: int,
):
    """dk/dv for one key block, accumulated over query blocks (transposed
    tiles: rows are keys, columns queries)."""
    kk, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_causal_overlap(qi, kk, block_q, block_k))
    def _body():
        # native-dtype operands, f32 accumulation (see _fwd_kernel note)
        st = jax.lax.dot_general(
            k_ref[0], q_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (BK, BQ) — transposed scores
        k_pos = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0
        )
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1
        )
        st = jnp.where(q_pos >= k_pos, st, NEG_INF)
        lse = lse_ref[0, 0, :][None, :]  # (1, BQ)
        pt = jnp.exp(st - jnp.where(lse == NEG_INF, 0.0, lse))  # (BK, BQ)
        dv_acc[:] += jax.lax.dot(
            pt.astype(do_ref.dtype), do_ref[0],
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v_ref[0], do_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BK, BQ) f32
        dst = pt * (dpt - delta_ref[0, 0, :][None, :])
        dk_acc[:] += jax.lax.dot(
            dst.astype(q_ref.dtype), q_ref[0],
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _to_bhsd(x):
    """(B, S, H, D) -> (B*H, S, D)."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bhsd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _block_sizes(
    s: int, block_q: int, block_k: int, interpret: bool
) -> tuple[int, int, int]:
    """Clamp blocks to the (8-aligned) sequence length and compute the pad
    that makes the padded length a multiple of both.

    On real TPU (``interpret=False``) Mosaic requires a block's lane dim to
    be a 128-multiple OR span the whole array, so sub-128 user block sizes
    are rounded up (the lse/delta row tiles put block_q in lanes).
    Interpreter mode has no tiling constraint — tests keep small blocks to
    exercise multi-block layouts on short sequences."""
    s8 = -(-max(8, s) // 8) * 8  # sublane alignment for small sequences

    def clamp(b: int) -> int:
        if not interpret:
            b = -(-b // 128) * 128
        return s8 if b >= s8 else b

    block_q, block_k = clamp(block_q), clamp(block_k)
    target = -(-s // block_q) * block_q
    target = -(-target // block_k) * block_k
    return block_q, block_k, target - s


def _fwd_impl(q, k, v, block_q, block_k, interpret, out_dtype=None):
    b, s, h, d = q.shape
    group = h // k.shape[2]  # query heads a KV head; 1: the old index map
    scale = 1.0 / (d ** 0.5)
    block_q, block_k, pad = _block_sizes(s, block_q, block_k, interpret)
    qf, kf, vf = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    if pad:
        # zero-padded tail keys sit above every real row's diagonal -> the
        # causal mask already excludes them; padded query rows are sliced
        # off below
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    n_q, n_k = sp // block_q, sp // block_k
    grid = (b * h, n_q, n_k)
    qspec = pl.BlockSpec(
        (1, block_q, d), lambda bh, qi, kk: (bh, qi, 0),
        memory_space=pltpu.VMEM,
    )
    kspec = pl.BlockSpec(
        (1, block_k, d),
        (lambda bh, qi, kk: (bh, kk, 0)) if group == 1
        # q's row b * H + head reads row b * KV + head // group of k and v
        else (lambda bh, qi, kk: (bh // group, kk, 0)),
        memory_space=pltpu.VMEM,
    )
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
            n_k=n_k,
        ),
        grid=grid,
        in_specs=[qspec, kspec, kspec],
        out_specs=[
            qspec,
            pl.BlockSpec(
                (1, 8, block_q), lambda bh, qi, kk: (bh, 0, qi),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sp, d), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((b * h, 8, sp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=interpret,
    )(qf, kf, vf)
    return out, lse, (qf, kf, vf), sp, pad


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal flash attention; (B, S, H, D) in and out.

    Numerically equivalent to
    :func:`..models.transformer.causal_attention` (tested to float
    tolerance) without ever materializing an (S, S) score matrix: peak
    attention temp is O(block_q * block_k) VMEM per core plus the O(S)
    logsumexp residual. ``interpret=None`` auto-selects interpreter mode
    off-TPU so the same code path tests on the CPU mesh.

    Use directly as ``TransformerConfig(attention_fn=flash_attention)``,
    or via :func:`make_flash_attention` to fix block sizes.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, _, _, _, pad = _fwd_impl(q, k, v, block_q, block_k, interpret)
    b, s, h, _ = q.shape
    if pad:
        out = out[:, :s, :]
    return _from_bhsd(out, b, h)


def _flash_fwd(q, k, v, block_q, block_k, interpret):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, lse, (qf, kf, vf), sp, pad = _fwd_impl(
        q, k, v, block_q, block_k, interpret
    )
    b, s, h, _ = q.shape
    out_user = out[:, :s, :] if pad else out
    return _from_bhsd(out_user, b, h), (qf, kf, vf, out, lse, q.shape)


def _flash_bwd(block_q, block_k, interpret, res, g):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qf, kf, vf, out, lse, qshape = res
    b, s, h, d = qshape
    bh, sp, _ = qf.shape
    block_q, block_k, _ = _block_sizes(s, block_q, block_k, interpret)
    scale = 1.0 / (d ** 0.5)
    n_q, n_k = sp // block_q, sp // block_k

    do = _to_bhsd(g)
    if sp != s:
        do = jnp.pad(do, ((0, 0), (0, sp - s), (0, 0)))
    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian diagonal term,
    # O(S) elementwise work outside the kernels. Stored (BH, 8, Sp) like
    # the lse (Mosaic row-vector tiling; see _fwd_kernel's flush note).
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (BH, Sp)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, sp))

    qspec = pl.BlockSpec(
        (1, block_q, d), lambda bh_, qi, kk: (bh_, qi, 0),
        memory_space=pltpu.VMEM,
    )
    kspec = pl.BlockSpec(
        (1, block_k, d), lambda bh_, qi, kk: (bh_, kk, 0),
        memory_space=pltpu.VMEM,
    )
    rowq = pl.BlockSpec(
        (1, 8, block_q), lambda bh_, qi, kk: (bh_, 0, qi),
        memory_space=pltpu.VMEM,
    )
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
            n_k=n_k,
        ),
        grid=(bh, n_q, n_k),
        in_specs=[qspec, kspec, kspec, qspec, rowq, rowq],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, sp, d), qf.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="flash_attention_dq",
        interpret=interpret,
    )(qf, kf, vf, do, lse, delta)

    # transposed grid: outer over key blocks, inner accumulates over the
    # query blocks at/below the diagonal
    qspec_t = pl.BlockSpec(
        (1, block_q, d), lambda bh_, kk, qi: (bh_, qi, 0),
        memory_space=pltpu.VMEM,
    )
    kspec_t = pl.BlockSpec(
        (1, block_k, d), lambda bh_, kk, qi: (bh_, kk, 0),
        memory_space=pltpu.VMEM,
    )
    rowq_t = pl.BlockSpec(
        (1, 8, block_q), lambda bh_, kk, qi: (bh_, 0, qi),
        memory_space=pltpu.VMEM,
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
            n_q=n_q,
        ),
        grid=(bh, n_k, n_q),
        in_specs=[qspec_t, kspec_t, kspec_t, qspec_t, rowq_t, rowq_t],
        out_specs=[kspec_t, kspec_t],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sp, d), kf.dtype),
            jax.ShapeDtypeStruct((bh, sp, d), vf.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        name="flash_attention_dkv",
        interpret=interpret,
    )(qf, kf, vf, do, lse, delta)

    if sp != s:
        dq, dk, dv = (a[:, :s, :] for a in (dq, dk, dv))
    return _from_bhsd(dq, b, h), _from_bhsd(dk, b, h), _from_bhsd(dv, b, h)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# The shortest prefill the kernel takes: the kernel alone against the dense
# form (with its copy of K and V to the query heads), us a layer on one v5e
# (PERF.md section 6, PR 37). Up to 512 positions the dense form is ahead by
# x 1.6-2.1 (16 float32 heads over 8: 9 / 15 / 29-38 us against 17 / 30 /
# 48-61 at 32 / 256 / 512: a grid step and the layout copies are a floor of
# some 17 us). From 1,024 the kernel is level or ahead at every shape
# measured: 82-91 against 86 (16 float32 heads over 8), 112 against 108 (20
# bfloat16 heads over 4), 621 against 154 (32 float32 heads over 8: past
# some 100 MB of scores the dense form falls off a cliff), and x 2.9-6.4 at
# 2,048 and 4,096.
_PREFILL_MIN_LEN = 1024


def prefill_takes_kernel(s: int, d: int) -> bool:
    """Whether a prefill of ``s`` positions at a head width of ``d`` attends
    through :func:`flash_attention_forward`: ``d`` a whole number of 128-lane
    tiles (toy widths stay dense: every tile of theirs is padded to 128), and
    ``s`` from the length at which the kernel alone no longer lost to the
    dense form on the chip. A rule on what the call can see, like
    ``decode_block``: no flag, no model's name."""
    return d % 128 == 0 and s >= _PREFILL_MIN_LEN


def flash_attention_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """The causal forward alone: ``q`` (B, S, H, D) against ``k`` / ``v``
    (B, S, KV, D) at their STORED head count, ``H`` a multiple of ``KV``; the
    result (B, S, H, D) in ``q``'s dtype. Not differentiable
    (:func:`flash_attention` is, at ``H == KV``).

    Tiles of 1,024 x 1,024, clamped to the length (v5e, PR 37, 32 heads over
    8 at 4,096 positions: 2.47 ms at 512 x 512, 2.23 at 1,024 x 512, 1.72 at
    512 x 1,024, 1.59 at 1,024 x 1,024, 1.75 at 1,024 x 2,048; a tile of
    512 x 512 is 0.7 us of MXU work under a rescale of the accumulator and a
    grid step that cost as much, so the key block counts for more; 2,048
    query rows pass the fast memory). The differentiable call keeps its
    512 x 512: its backward kernels were measured there.

    On the chip float32 operands are rounded to bfloat16 here, before the
    kernel's DMA, and not in it: the MXU multiplies float32 operands in one
    bfloat16 pass either way (the kernel's note), as it does for the dense
    form under XLA's default precision, whose compiled program rounds q, k
    and v where they are made. No product changes, a tile moves half the
    bytes (1.44 ms for 1.59 above). Scores, softmax and the accumulator are
    float32. Off the chip (the interpreter) nothing is rounded, as XLA's
    default precision rounds nothing there."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, _ = q.shape
    if h % k.shape[2]:
        raise ValueError(
            f"{h} query heads are no multiple of {k.shape[2]} KV heads"
        )
    out_dtype = q.dtype
    if not interpret and out_dtype == jnp.float32:
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out, _, _, _, pad = _fwd_impl(
        q, k, v, block_q, block_k, interpret, out_dtype
    )
    if pad:
        out = out[:, :s, :]
    return _from_bhsd(out, b, h)


def make_flash_attention(
    block_q: int = 512, block_k: int = 512, interpret: bool | None = None
):
    """Fix kernel block sizes; returns an ``attention_fn(q, k, v)`` for
    :class:`..models.transformer.TransformerConfig`."""

    def attention_fn(q, k, v):
        return flash_attention(
            q, k, v, block_q, block_k, interpret
        )

    return attention_fn

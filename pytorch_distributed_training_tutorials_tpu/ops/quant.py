"""Int8 quantization + Pallas int8 matmul: the bitsandbytes twin.

Reference capability (SURVEY.md C13): ``from_pretrained(...,
BitsAndBytesConfig(load_in_8bit=True))`` loads Llama-7B with int8 matmul
weights and float16 norms (``03.model_parallel.ipynb`` cell 2; param audit
cell 4). The TPU-native equivalent implemented here:

- :func:`quantize_int8` — per-channel symmetric weight quantization
  (absmax / 127, the bitsandbytes vector-wise scheme) into an
  :class:`Int8Param` pytree leaf.
- :func:`int8_matmul` — a Pallas TPU kernel computing
  ``x @ dequant(q, scale)`` the LLM.int8 way: activations are quantized
  per-row *inside* the kernel, the MXU runs a true int8 x int8 -> int32
  matmul, and the int32 accumulator is dequantized by the outer product of
  row and column scales. HBM traffic for the weight is 1/4 of f32 — the
  point of 8-bit serving. Runs in interpreter mode off-TPU so tests are
  hardware-free (and cross-checked against the pure-jnp reference math).
- :class:`Int8Dense` — drop-in serving twin of ``nn.Dense`` over an
  :class:`Int8Param` (+f32 bias), for checkpoint-quantized models (see
  :func:`..parallel.auto.load_quantized`, the ``load_in_8bit`` seam).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax import struct
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_training_tutorials_tpu.utils.compat import (
    shard_map_nocheck,
)


class Int8Param(struct.PyTreeNode):
    """Per-channel symmetric int8 weight: ``w ~= q * scale``.

    ``q``: int8, same shape as the original weight. ``scale``: float32,
    shape broadcastable to ``q`` (1 everywhere except the channel axis).
    """

    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.q.shape

    def dequantize(self) -> jax.Array:
        return self.q.astype(jnp.float32) * self.scale


def quantize_int8(
    w: jax.Array, channel_axis: int = -1, reduce_axis: int | None = None
) -> Int8Param:
    """absmax/127 per-channel symmetric quantization (the bitsandbytes
    vector-wise scheme). ``channel_axis`` is the output-feature axis that
    keeps its own scale (-1 for a Dense kernel (in, out)). With
    ``reduce_axis`` the absmax is taken over that axis alone and every
    other axis keeps its own scales too: a stack of kernels
    ``(..., in, out)`` with ``reduce_axis=-2`` is each kernel quantized on
    its own."""
    w = jnp.asarray(w, jnp.float32)
    if reduce_axis is not None:
        reduce_axes = (reduce_axis % w.ndim,)
    else:
        reduce_axes = tuple(
            a for a in range(w.ndim) if a != channel_axis % w.ndim
        )
    absmax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return Int8Param(q=q, scale=scale)


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int4 values (any int dtype, range [-7, 7]) two-per-byte along
    the last axis: uint8 byte ``j`` holds element ``j`` in the low nibble
    and element ``j + D/2`` in the high nibble (the HALF-SPLIT layout —
    unpacking is one mask, one shift, and a concatenate, with no
    elementwise interleave for Mosaic to scalarize; the same front/back
    split :func:`..models.transformer.apply_rope` uses). Last axis must be
    even; output shape ``(..., D // 2)``.

    Reference capability (SURVEY.md C13 lineage): the 4-bit half of the
    bitsandbytes load_in_*bit family (``/root/reference/
    03.model_parallel.ipynb`` cell 2 loads the 8-bit variant; int4 is the
    same absmax scheme at half the bits). Inverse: :func:`unpack_int4`.
    """
    d = q.shape[-1]
    if d % 2:
        raise ValueError(f"pack_int4 needs an even last axis, got {d}")
    u = q.astype(jnp.uint8) & 0xF  # two's-complement nibble
    lo, hi = u[..., : d // 2], u[..., d // 2 :]
    return lo | (hi << 4)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`: uint8 ``(..., D/2)`` -> int8
    ``(..., D)``. Each nibble sign-extends through the two's-complement
    rule ``n >= 8 -> n - 16`` (branch-free ``jnp.where`` — values are
    traced data). Bytes already widened to int32 (the paged kernel: the
    TPU vector unit has no 8-bit shifts) unpack to int32."""
    out = jnp.int8 if packed.dtype == jnp.uint8 else packed.dtype
    lo = (packed & 0xF).astype(out)
    hi = ((packed >> 4) & 0xF).astype(out)
    ext = lambda n: jnp.where(n >= 8, n - 16, n)  # noqa: E731
    return jnp.concatenate([ext(lo), ext(hi)], axis=-1)


def quantize_kv_int4(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """int4 twin of ``models.transformer._quantize_kv``: quantize K/V
    ``(..., D)`` to packed int4 (two nibbles per byte, :func:`pack_int4`)
    with per-token-per-head scales (absmax over the head_dim vector /
    7 — the symmetric absmax scheme of :func:`quantize_int8` at 4 bits).

    Scales are stored **bfloat16**, not f32: that makes an int4 cache
    entry cost exactly half its int8 twin per token-head (``D/2 + 2``
    bytes vs ``D + 4``) — the "2x pages at equal HBM" claim is exact, not
    approximate. Quantization divides by the ROUNDED bf16 scale so
    dequantization with the stored scale is exact (no f32-vs-bf16 scale
    mismatch); bf16's 8 mantissa bits are noise next to the ~1/15
    relative step of 4-bit values. Inverse: :func:`dequantize_kv_int4`.
    """
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1)
    scale = (jnp.maximum(absmax, 1e-8) / 7.0).astype(jnp.bfloat16)
    q = jnp.clip(
        jnp.round(x32 / scale.astype(jnp.float32)[..., None]), -7, 7
    ).astype(jnp.int8)
    return pack_int4(q), scale


def dequantize_kv_int4(packed: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Packed int4 cache + bf16 scales -> compute dtype (the
    ``_dequantize_kv`` twin). The unpack + multiply is elementwise, so XLA
    fuses it into the attention matmuls' operand reads on the gather
    path; the Pallas kernel (:mod:`.paged_attention`) runs the same
    nibble math per page tile in VMEM — this function is its numerics
    reference."""
    q = unpack_int4(packed)
    return (
        q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
    ).astype(dtype)


def _int8_matmul_kernel(*refs, n_k: int):
    """One (TM, TN, TK) tile: quantize the x tile per row, int8 MXU matmul,
    accumulate the dequantized partial in f32 VMEM scratch; write out on the
    last K tile. A scalar-prefetched layer index, where the weights are a
    stack, comes first and only steers the index maps."""
    x_ref, q_ref, sw_ref, out_ref, acc_ref = refs[-5:]
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:].astype(jnp.float32)  # (TM, TK)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # (TM, 1)
    sx = jnp.maximum(absmax, 1e-8) / 127.0
    xq = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    part = jnp.dot(
        xq, q_ref[:], preferred_element_type=jnp.int32
    )  # int8 x int8 -> int32 on the MXU
    acc_ref[:] += part.astype(jnp.float32) * sx

    @pl.when(kk == n_k - 1)
    def _flush():
        out_ref[:] = acc_ref[:] * sw_ref[:]


# what a grid step of int8_matmul should move, and what its blocks may take
# of the 16 MB of fast memory the chip's compiler gives a kernel (the rest
# is for the kernel body's own int32 and float32 temporaries)
_STEP_BYTES = 1 << 20
_VMEM_BYTES = 12 << 20


def _n_block(block_m: int, block_k: int) -> int:
    """The N block :func:`int8_matmul` takes where the caller names none
    (the call clamps it to N): the columns that make a weight tile of
    ``_STEP_BYTES`` at this K tile (2048 at 512 rows), halved while the
    call's blocks (x, weight, scale and result tiles double-buffered, the
    float32 accumulator) exceed ``_VMEM_BYTES``. A grid step costs about
    0.35 us whatever it moves: at 128 KB a step the kernel streams int8
    weights at 260-285 GB/s, at 1 MB at 615-705 (one v5e chip, PERF.md
    section 6, PR 33 and PR 35)."""

    def blocks_bytes(bn):
        tiles = 4 * block_m * block_k + block_k * bn + 4 * bn + 4 * block_m * bn
        return 2 * tiles + 4 * block_m * bn

    block_n = max(128, _STEP_BYTES // block_k // 128 * 128)
    while block_n > 128 and blocks_bytes(block_n) > _VMEM_BYTES:
        block_n //= 2
    return block_n


def int8_matmul(
    x: jax.Array,
    w: Int8Param,
    layer: jax.Array | None = None,
    *,
    block_m: int = 256,
    block_n: int | None = None,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """``x @ (q * scale)`` with dynamic per-(row, K-tile) int8 activation
    quantization.

    ``x``: (M, K) float; ``w.q``: (K, N) int8 with per-column ``w.scale``.
    The contraction is **K-blocked**: each (TM, TN) output tile accumulates
    over K in ``block_k`` slabs through an f32 VMEM scratch accumulator, so
    VMEM residency is ``O(TM*TK + TK*TN + TM*TN)`` regardless of K —
    Llama-7B widths (K=4096, N=11008 and the transpose) fit comfortably
    where the old whole-K layout overflowed the ~16 MB VMEM budget.

    Activations quantize per (row, K-tile) rather than per full row — a
    strictly finer-grained scheme than LLM.int8's vector-wise scaling (each
    tile gets its own absmax), matched exactly by
    :func:`int8_matmul_reference` with the same ``block_k``.

    **The weights are read where they lie.** With ``layer`` (an int32
    scalar, traced) ``w.q`` is a stack (L, K, N) with ``w.scale``
    (L, 1, N), as ``nn.scan`` keeps the layers' weights, and the product is
    layer ``layer``'s: the index rides scalar prefetch and the weight and
    scale blocks are addressed at ``(layer, kk, j)`` in the stack itself,
    viewed (L*K, N), so no slice of it is ever made. That needs K in whole
    K tiles (rows past K would be the next layer's); a stack of another K
    (toy widths) is sliced and taken as a (K, N) weight. A ragged N needs
    no copy either: the last column block hangs over the edge (columns are
    independent: what lies past N reaches no kept result). M is padded to
    its tile, and K where it is no multiple of 128 (zero rows and columns
    contribute nothing), so any M, K, N works.

    ``block_n`` is no part of the arithmetic (a column's sum runs over the
    same K tiles in the same order whatever columns share its block).
    Unset, :func:`_n_block` works it out from the M and K tiles, the same
    for a (K, N) weight and for a stack, and N in whole lane tiles caps it.
    ``interpret=None`` auto-selects interpreter mode off-TPU so the same
    code path tests on CPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = x.shape
    kq, n = w.q.shape[-2:]
    assert k == kq and w.q.ndim == (2 if layer is None else 3), (
        x.shape, w.q.shape, layer
    )
    block_k = min(block_k, max(128, k))
    block_k = -(-block_k // 128) * 128
    pad_k = (-k) % block_k
    if layer is not None and pad_k:
        # rows past k in the last K tile would be the next layer's
        return int8_matmul(
            x, Int8Param(q=w.q[layer], scale=w.scale[layer]),
            block_m=block_m, block_n=block_n, block_k=block_k,
            interpret=interpret,
        )

    # sublane alignment: f32 blocks need second-to-last dim % 8 == 0 on real
    # TPU (interpret mode would hide a violation); K tiles stay % 128 (lane
    # dim of x, sublane-int8 dim of q)
    block_m = min(block_m, max(8, m))
    block_m = -(-block_m // 8) * 8
    # N is the lane dim of the output/q blocks: round up to 128 like K (an
    # odd-vocab lm_head must not hand the real-TPU kernel a sub-lane tile;
    # the last block hangs over N)
    if block_n is None:
        block_n = _n_block(block_m, block_k)
    block_n = min(block_n, max(128, n))
    block_n = -(-block_n // 128) * 128
    pad_m = (-m) % block_m
    want = (1, n) if layer is None else (w.q.shape[0], 1, n)
    if tuple(w.scale.shape) not in (want, (n,)):
        raise ValueError(
            f"int8_matmul needs per-output-column scales of size {n} "
            f"(quantize with channel_axis=-1); got scale shape "
            f"{tuple(w.scale.shape)}"
        )
    scale = w.scale.reshape(want).astype(jnp.float32)
    if pad_m or pad_k:
        x = jnp.pad(x, ((0, pad_m), (0, pad_k)))
    q = jnp.pad(w.q, ((0, pad_k), (0, 0))) if pad_k else w.q
    mp = m + pad_m
    n_k = (k + pad_k) // block_k

    if layer is None:
        prefetch = ()
        q_map = lambda i, j, kk: (kk, j)  # noqa: E731
        scale_spec = pl.BlockSpec(
            (1, block_n), lambda i, j, kk: (0, j), memory_space=pltpu.VMEM
        )
    else:
        # rank 2: layer l's K tile kk is row block l * n_k + kk of the stack
        prefetch = (jnp.asarray(layer, jnp.int32).reshape(1),)
        q = q.reshape(-1, n)
        q_map = lambda i, j, kk, l: (l[0] * n_k + kk, j)  # noqa: E731
        scale_spec = pl.BlockSpec(
            (None, 1, block_n), lambda i, j, kk, l: (l[0], 0, j),
            memory_space=pltpu.VMEM,
        )
    out = pl.pallas_call(
        functools.partial(_int8_matmul_kernel, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(mp // block_m, pl.cdiv(n, block_n), n_k),
            in_specs=[
                pl.BlockSpec(
                    (block_m, block_k),
                    lambda i, j, kk, *_: (i, kk),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (block_k, block_n), q_map, memory_space=pltpu.VMEM
                ),
                scale_spec,
            ],
            out_specs=pl.BlockSpec(
                (block_m, block_n),
                lambda i, j, kk, *_: (i, j),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        name="int8_matmul",
        interpret=interpret,
    )(*prefetch, x.astype(jnp.float32), q, scale)
    return out[:m] if pad_m else out


def int8_matmul_tp(
    x: jax.Array,
    w: Int8Param,
    mesh: Mesh,
    *,
    kind: str,
    axis: str = "model",
    data_axis: str = "data",
) -> jax.Array:
    """Tensor-parallel ``x @ (q * scale)``: the Pallas kernel under an
    explicit :func:`jax.shard_map` (a ``pallas_call`` is a single-device
    program — GSPMD cannot partition it, so the Megatron split is stated
    here rather than propagated).

    The int8 twin of the float TP layout
    (:data:`..models.transformer.TP_RULES`):

    - ``kind="column"``: ``q`` (K, N) and per-column ``scale`` split over
      ``axis`` on N; every device runs the full-K kernel on its column
      shard. Activation quantization sees the same (row, K-tile) groups as
      the unsharded kernel — numerics are identical.
    - ``kind="row"``: ``q`` split over ``axis`` on K, ``scale`` replicated;
      each device multiplies its K-shard (activations arrive feature-
      sharded from the previous column layer) and a ``psum`` over ``axis``
      sums the partials — the one allreduce per residual branch. Activation
      quantization groups are per (row, *local* K-tile), a regrouping of
      the unsharded kernel's tiles: same error scale, bit-different values
      (``tests/test_quant.py`` pins the sharded math exactly against a
      per-shard reference composition).

    ``x``: (M, K) with rows optionally sharded over ``data_axis`` (M must
    then divide by it). Requires N (column) / K (row) divisible by the
    ``axis`` size. Serving-only, like the kernel itself.
    """
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no {axis!r} axis: {dict(mesh.shape)}")
    n_shards = mesh.shape[axis]
    m, k = x.shape
    _, n = w.q.shape
    scale_row = w.scale.reshape(1, n).astype(jnp.float32)
    # shard rows over the data axis only when they divide it — a decode
    # step's M is batch*1 and need not match the mesh (replicated rows are
    # correct, just unsharded work)
    dspec = (
        data_axis
        if data_axis in mesh.shape and m % mesh.shape[data_axis] == 0
        else None
    )

    if kind == "column":
        if n % n_shards:
            raise ValueError(f"column split needs N ({n}) % {n_shards} == 0")
        in_specs = (P(dspec, None), P(None, axis), P(None, axis))
        out_specs = P(dspec, axis)

        def f(xl, ql, sl):
            return int8_matmul(xl, Int8Param(q=ql, scale=sl))

    elif kind == "row":
        if k % n_shards:
            raise ValueError(f"row split needs K ({k}) % {n_shards} == 0")
        in_specs = (P(dspec, axis), P(axis, None), P(None, None))
        out_specs = P(dspec, None)

        def f(xl, ql, sl):
            part = int8_matmul(xl, Int8Param(q=ql, scale=sl))
            return jax.lax.psum(part, axis)

    else:
        raise ValueError(f"kind must be 'column' or 'row', got {kind!r}")

    # checking off: pallas_call outputs carry no varying-axes info for
    # shard_map's static checker (check_vma)
    return shard_map_nocheck(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )(x, w.q, scale_row)


def int8_matmul_reference(
    x: jax.Array, w: Int8Param, *, block_k: int = 512
) -> jax.Array:
    """Pure-jnp statement of the kernel's math (for tests and off-TPU use):
    per-(row, K-tile) activation quantization with the same ``block_k``
    tiling as :func:`int8_matmul`, f32 accumulation across tiles."""
    x = jnp.asarray(x, jnp.float32)
    m, k = x.shape
    block_k = min(block_k, max(128, k))
    block_k = -(-block_k // 128) * 128
    pad_k = (-k) % block_k
    if pad_k:
        x = jnp.pad(x, ((0, 0), (0, pad_k)))
    q = jnp.pad(w.q, ((0, pad_k), (0, 0))) if pad_k else w.q
    acc = jnp.zeros((m, q.shape[1]), jnp.float32)
    for lo in range(0, k + pad_k, block_k):
        xt = x[:, lo : lo + block_k]
        absmax = jnp.max(jnp.abs(xt), axis=1, keepdims=True)
        sx = jnp.maximum(absmax, 1e-8) / 127.0
        xq = jnp.clip(jnp.round(xt / sx), -127, 127).astype(jnp.int8)
        part = jnp.dot(
            xq, q[lo : lo + block_k], preferred_element_type=jnp.int32
        )
        acc = acc + part.astype(jnp.float32) * sx
    return acc * w.scale.reshape(1, -1)


def _int8_affine(
    mod: nn.Module, x, feats: tuple, n_in: int, use_bias: bool, stacked=None
):
    """The shared body of the int8 serving layers: flattened 2-D ``q`` +
    per-column ``scale`` params, the K-blocked MXU matmul, reshape, bias —
    one copy for Int8Dense and Int8DenseGeneral. With ``mod.mesh`` +
    ``mod.shard_kind`` set (and the axis really in the mesh), the matmul
    runs tensor-parallel through :func:`int8_matmul_tp`. ``stacked``, under
    the layer scan, is ``(Int8Param, layer)``: the stack this layer's ``q``
    and ``scale`` were sliced from and the layer's index, which the kernel
    reads in place of the slices (they are dead then, and never made)."""
    in_dims = x.shape[x.ndim - n_in :]
    k = 1
    for d in in_dims:
        k *= d
    n_out = 1
    for f in feats:
        n_out *= f
    q = mod.param("q", nn.initializers.zeros, (k, n_out), jnp.int8)
    scale = mod.param(
        "scale", nn.initializers.ones, (1, n_out), jnp.float32
    )
    lead = x.shape[: x.ndim - n_in]
    w = Int8Param(q=q, scale=scale)
    x2 = x.reshape(-1, k)
    mesh = getattr(mod, "mesh", None)
    if (
        mesh is not None
        and mod.shard_kind is not None
        and mesh.shape.get(mod.shard_axis, 1) > 1
    ):
        out2 = int8_matmul_tp(
            x2, w, mesh, kind=mod.shard_kind, axis=mod.shard_axis
        )
    elif stacked is not None:
        out2 = int8_matmul(x2, *stacked)
    else:
        out2 = int8_matmul(x2, w)
    out = out2.reshape(*lead, *feats)
    if use_bias:
        out = out + mod.param(
            "bias", nn.initializers.zeros, feats, jnp.float32
        )
    return out.astype(x.dtype)


class Int8Dense(nn.Module):
    """Serving twin of ``nn.Dense`` over int8 weights.

    Parameters are ``q`` (int8 kernel), ``scale`` (per-output-column), and
    optionally ``bias`` — the tree produced by quantizing a trained Dense
    kernel (:func:`quantize_int8` / :func:`..parallel.auto.load_quantized`).
    Zero-initialized when built fresh: this module is for loading quantized
    checkpoints, not training (int8 has no useful gradient).

    ``mesh`` + ``shard_kind`` ('column' | 'row') switch the matmul to the
    tensor-parallel :func:`int8_matmul_tp`; param shardings come from
    :data:`..models.transformer.INT8_TP_RULES`.
    """

    features: int
    use_bias: bool = True
    mesh: Mesh | None = None
    shard_kind: str | None = None
    shard_axis: str = "model"

    @nn.compact
    def __call__(self, x, stacked=None):
        return _int8_affine(
            self, x, (self.features,), 1, self.use_bias, stacked
        )


class Int8DenseGeneral(nn.Module):
    """Serving twin of ``nn.DenseGeneral`` over int8 weights.

    Supports the two transformer shapes: ``axis=-1`` with tuple features
    (the q/k/v projections, ``d_model -> (H, D)``) and ``axis=(-2, -1)``
    (the o projection, ``(H, D) -> d_model``). The kernel is stored
    flattened 2-D (``q``: (in, prod(features)) int8 + per-column scales) so
    the K-blocked MXU kernel serves every case.
    """

    features: int | tuple[int, ...]
    axis: int | tuple[int, ...] = -1
    use_bias: bool = False
    mesh: Mesh | None = None
    shard_kind: str | None = None
    shard_axis: str = "model"

    @nn.compact
    def __call__(self, x, stacked=None):
        feats = (
            self.features
            if isinstance(self.features, tuple)
            else (self.features,)
        )
        axes = self.axis if isinstance(self.axis, tuple) else (self.axis,)
        return _int8_affine(
            self, x, feats, len(axes), self.use_bias, stacked
        )


def _grouped_int8_kernel(te_ref, x_ref, q_ref, sw_ref, out_ref, acc_ref, *,
                         n_k: int):
    """One (TM, TN, TK) tile of :func:`grouped_int8_matmul`: the
    ``_int8_matmul_kernel`` body against the weight block of the row
    tile's own expert (``te_ref`` only steers the index maps)."""
    del te_ref
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:].astype(jnp.float32)  # (TM, TK)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    sx = jnp.maximum(absmax, 1e-8) / 127.0
    xq = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    part = jnp.dot(xq, q_ref[0], preferred_element_type=jnp.int32)
    acc_ref[:] += part.astype(jnp.float32) * sx

    @pl.when(kk == n_k - 1)
    def _flush():
        out_ref[:] = (acc_ref[:] * sw_ref[0]).astype(out_ref.dtype)


def _dividing_block(dim: int, cap: int) -> int:
    """Largest multiple of 128 that divides ``dim`` (itself a multiple of
    128) and is at most ``cap``."""
    lanes = dim // 128
    best = max(f for f in range(1, min(lanes, cap // 128) + 1) if lanes % f == 0)
    return 128 * best


def grouped_int8_matmul(
    x: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    tile_expert: jax.Array,
    n_tiles: jax.Array,
    *,
    block_m: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Row tile ``i`` of ``x`` times the int8 weights of expert
    ``tile_expert[i]``, for the first ``n_tiles`` row tiles: the grouped
    product of an expert layer whose rows are sorted by expert, each
    expert's rows padded to a multiple of ``block_m``
    (:func:`..models.moe.plan_dispatch` makes the plan).

    ``x``: (M, K) float with M a multiple of ``block_m``; ``q``: (E, K, N)
    int8 with ``scale`` (E, 1, N) float32 a column; ``tile_expert``:
    (M // block_m,) int32; ``n_tiles``: int32 scalar, TRACED. The grid's
    leading bound is ``n_tiles`` itself, so the work is the tiles that hold
    routed rows, not the static worst case M; rows of the tiles beyond are
    left unwritten (whatever the buffer held: mask them, do not multiply
    them by nought). Same numerics as :func:`int8_matmul`: activations
    rounded to int8 per (row, K tile), int8 x int8 -> int32 on the MXU,
    float32 accumulation, one scale a column. K and N are padded to
    multiples of 128 where they are not (toy widths only: a copy of the
    weights).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = x.shape
    e, kq, n = q.shape
    assert k == kq and m % block_m == 0, (x.shape, q.shape, block_m)
    assert tuple(scale.shape) == (e, 1, n), (scale.shape, q.shape)
    pad_k, pad_n = (-k) % 128, (-n) % 128
    if pad_k:
        x = jnp.pad(x, ((0, 0), (0, pad_k)))
    if pad_k or pad_n:
        q = jnp.pad(q, ((0, 0), (0, pad_k), (0, pad_n)))
        scale = jnp.pad(scale, ((0, 0), (0, 0), (0, pad_n)), constant_values=1.0)
    block_k = _dividing_block(k + pad_k, 2048)
    block_n = _dividing_block(n + pad_n, 1024)
    n_k = (k + pad_k) // block_k
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, (n + pad_n) // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk, te: (i, kk)),
            pl.BlockSpec(
                (1, block_k, block_n), lambda i, j, kk, te: (te[i], kk, j)
            ),
            pl.BlockSpec((1, 1, block_n), lambda i, j, kk, te: (te[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk, te: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_int8_kernel, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n + pad_n), x.dtype),
        name="grouped_int8_matmul",
        interpret=interpret,
    )(tile_expert, x, q, scale)
    return out[:, :n] if pad_n else out


class Int8ExpertStack(nn.Module):
    """The int8 weights of the experts a chip holds for one projection,
    ``q`` (experts, k, n) with ``scale`` (experts, 1, n), applied to rows
    sorted by expert through :func:`grouped_int8_matmul`. Serving only,
    like :class:`Int8Dense`."""

    experts: int
    k: int
    n: int

    @nn.compact
    def __call__(self, rows, tile_expert, n_tiles, block_m: int):
        q = self.param(
            "q", nn.initializers.zeros, (self.experts, self.k, self.n), jnp.int8
        )
        scale = self.param(
            "scale", nn.initializers.ones, (self.experts, 1, self.n),
            jnp.float32,
        )
        return grouped_int8_matmul(
            rows, q, scale, tile_expert, n_tiles, block_m=block_m
        )

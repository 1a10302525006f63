"""Int8 quantization + pallas int8 matmul (interpreter mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers import pallas_operands

from pytorch_distributed_training_tutorials_tpu.ops.quant import (
    Int8Dense,
    Int8Param,
    int8_matmul,
    int8_matmul_reference,
    int8_matmul_tp,
    quantize_int8,
)
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh


def _w(shape, seed=0):
    rng = np.random.Generator(np.random.PCG64(seed))
    return rng.standard_normal(shape).astype(np.float32)


def test_quantize_roundtrip_error_bounded():
    w = _w((256, 128))
    qp = quantize_int8(w)
    assert qp.q.dtype == jnp.int8
    assert qp.scale.shape == (1, 128)
    # per-channel absmax/127: error <= scale/2 per element
    err = np.abs(np.asarray(qp.dequantize()) - w)
    assert (err <= np.asarray(qp.scale) / 2 + 1e-7).all()


def test_quantize_channel_axis():
    w = _w((64, 32))
    qp = quantize_int8(w, channel_axis=0)
    assert qp.scale.shape == (64, 1)
    cols = np.abs(np.asarray(qp.dequantize()) - w)
    assert (cols <= np.asarray(qp.scale) / 2 + 1e-7).all()


def test_int8_matmul_matches_reference_math():
    """Pallas kernel (interpret) == the pure-jnp statement of its math."""
    x = _w((48, 256), seed=1)  # M=48 exercises the pad-to-tile path
    qp = quantize_int8(_w((256, 128), seed=2))
    got = int8_matmul(jnp.asarray(x), qp, block_m=32, block_n=128,
                      interpret=True)
    want = int8_matmul_reference(jnp.asarray(x), qp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_int8_matmul_ragged_n_padded_correctly():
    """N not a multiple of block_n: tail columns must be real values."""
    x = _w((16, 128), seed=6)
    qp = quantize_int8(_w((128, 300), seed=7))  # 300 % 256 != 0
    got = int8_matmul(jnp.asarray(x), qp, interpret=True)
    want = int8_matmul_reference(jnp.asarray(x), qp)
    assert got.shape == (16, 300)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_int8_matmul_rejects_row_scales():
    import pytest

    x = jnp.asarray(_w((8, 64), seed=8))
    qp = quantize_int8(_w((64, 64), seed=9), channel_axis=0)  # row scales
    with pytest.raises(ValueError, match="per-output-column"):
        int8_matmul(x, qp, interpret=True)


def test_int8_matmul_close_to_f32():
    """End-to-end quantization error stays small relative to f32 matmul."""
    x = _w((32, 512), seed=3)
    w = _w((512, 256), seed=4)
    got = np.asarray(int8_matmul(jnp.asarray(x), quantize_int8(w),
                                 interpret=True))
    want = x @ w
    rel = np.abs(got - want).mean() / np.abs(want).mean()
    assert rel < 0.02, rel  # two int8 quantizations, ~1% expected


def test_int8_dense_serving_matches_dense():
    """Quantize a trained Dense kernel into Int8Dense params: outputs match
    to quantization error — the load_in_8bit serving path."""
    from flax import linen as nn

    x = _w((16, 128), seed=5)
    dense = nn.Dense(64)
    variables = dense.init(jax.random.PRNGKey(0), jnp.asarray(x))
    f32_out = dense.apply(variables, jnp.asarray(x))

    qp = quantize_int8(variables["params"]["kernel"])
    q_params = {
        "q": qp.q,
        "scale": qp.scale.reshape(1, -1),
        "bias": variables["params"]["bias"],
    }
    q_out = Int8Dense(64).apply({"params": q_params}, jnp.asarray(x))
    rel = np.abs(np.asarray(q_out) - np.asarray(f32_out)).mean() / (
        np.abs(np.asarray(f32_out)).mean()
    )
    assert rel < 0.02, rel


def test_load_quantized_checkpoint(tmp_path):
    """Checkpoint -> int8-on-load restore -> audit shows int8 matmul weights
    and float everything else (the 03-notebook cell-4 audit, TPU-style)."""
    from pytorch_distributed_training_tutorials_tpu.parallel.auto import (
        load_quantized,
        save_checkpoint,
    )

    tree = {
        "block": {
            "attn": {"kernel": _w((64, 64)), "bias": _w((64,))},
            "norm": {"scale": _w((64,))},
        }
    }
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, tree)
    loaded = load_quantized(path)
    attn = loaded["block"]["attn"]
    assert isinstance(attn["kernel"], Int8Param)
    assert attn["kernel"].q.dtype == jnp.int8
    assert attn["bias"].dtype == np.float32  # untouched
    assert loaded["block"]["norm"]["scale"].dtype == np.float32
    np.testing.assert_allclose(
        np.asarray(attn["kernel"].dequantize()),
        tree["block"]["attn"]["kernel"],
        atol=float(np.asarray(attn["kernel"].scale).max()) / 2 + 1e-7,
    )


def test_int8_matmul_k_blocked_multi_tile():
    """K > block_k exercises the VMEM scratch accumulator across K tiles;
    kernel must equal the reference math exactly (same tiling)."""
    rng = np.random.Generator(np.random.PCG64(7))
    x = rng.standard_normal((16, 384)).astype(np.float32)
    w = quantize_int8(rng.standard_normal((384, 64)).astype(np.float32))
    out = int8_matmul(x, w, block_m=8, block_n=64, block_k=128)
    ref = int8_matmul_reference(x, w, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=1e-4)


def test_int8_matmul_ragged_k_padded_correctly():
    """K not a multiple of 128 (the ADVICE round-1 finding): the kernel pads
    K with zero columns/rows, which contribute nothing."""
    rng = np.random.Generator(np.random.PCG64(8))
    x = rng.standard_normal((8, 300)).astype(np.float32)
    w = quantize_int8(rng.standard_normal((300, 32)).astype(np.float32))
    out = int8_matmul(x, w, block_m=8, block_n=32, block_k=128)
    ref = int8_matmul_reference(x, w, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=1e-4)
    # and the quantization error vs the f32 product stays int8-sized
    f32 = x @ np.asarray(w.dequantize())
    err = np.abs(np.asarray(out) - f32).max()
    assert err < 0.05 * np.abs(f32).max() + 1e-3


def test_int8_matmul_llama_width_tiles():
    """Llama-7B d_ff geometry scaled to interpreter speed: K=2048 x N=688
    with production-shaped (256, 256, 512) tiles — 4 K-slabs through the
    scratch accumulator plus ragged-N padding. The VMEM working set this
    implies on hardware is blocks only (~0.9 MB), independent of K/N."""
    rng = np.random.Generator(np.random.PCG64(9))
    x = rng.standard_normal((32, 2048)).astype(np.float32)
    w = quantize_int8(rng.standard_normal((2048, 688)).astype(np.float32))
    out = int8_matmul(x, w, block_m=256, block_n=256, block_k=512)
    ref = int8_matmul_reference(x, w, block_k=512)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=1e-4)


def test_int8_matmul_tp_column_exact():
    """Column split doesn't change activation-quantization grouping: the
    TP kernel must equal the unsharded kernel bit-for-bit (float tol)."""
    mesh = create_mesh({"data": 2, "model": 4})
    x = jnp.asarray(_w((16, 256), seed=10))
    w = quantize_int8(jnp.asarray(_w((256, 512), seed=11)))
    np.testing.assert_allclose(
        np.asarray(int8_matmul_tp(x, w, mesh, kind="column")),
        np.asarray(int8_matmul(x, w)),
        rtol=1e-6, atol=1e-6,
    )


def test_int8_matmul_tp_row_matches_shard_composition():
    """Row split quantizes activations per (row, local K-tile); the exact
    statement of its math is the psum of per-shard reference matmuls."""
    mesh = create_mesh({"data": 2, "model": 4})
    x = jnp.asarray(_w((16, 256), seed=12))
    w = quantize_int8(jnp.asarray(_w((256, 512), seed=13)))
    out = int8_matmul_tp(x, w, mesh, kind="row")
    kk = 256 // 4
    exp = sum(
        np.asarray(
            int8_matmul_reference(
                x[:, i * kk : (i + 1) * kk],
                Int8Param(q=w.q[i * kk : (i + 1) * kk], scale=w.scale),
            )
        )
        for i in range(4)
    )
    np.testing.assert_allclose(np.asarray(out), exp, rtol=1e-4, atol=1e-4)
    # regrouping error stays int8-sized vs the unsharded kernel
    base = np.asarray(int8_matmul(x, w))
    assert np.abs(np.asarray(out) - base).max() < 0.05 * np.abs(base).max()


def test_int8_matmul_tp_validates():
    import pytest

    mesh = create_mesh({"data": 8})
    x = jnp.asarray(_w((8, 64), seed=1))
    w = quantize_int8(jnp.asarray(_w((64, 64), seed=2)))
    with pytest.raises(ValueError, match="no 'model' axis"):
        int8_matmul_tp(x, w, mesh, kind="column")
    mesh2 = create_mesh({"model": 8})
    with pytest.raises(ValueError, match="column split needs"):
        int8_matmul_tp(x, quantize_int8(jnp.asarray(_w((64, 36), 3))), mesh2, kind="column")
    with pytest.raises(ValueError, match="row split needs"):
        int8_matmul_tp(
            jnp.asarray(_w((8, 36), 4)),
            quantize_int8(jnp.asarray(_w((36, 64), 5))),
            mesh2, kind="row",
        )
    with pytest.raises(ValueError, match="kind must be"):
        int8_matmul_tp(x, w, mesh2, kind="diag")


def _stack(layers, k, n, seed=20):
    """``layers`` kernels (k, n), each quantized on its own, stacked as
    ``nn.scan`` keeps them: ``q`` (L, k, n), ``scale`` (L, 1, n)."""
    return quantize_int8(_w((layers, k, n), seed=seed), reduce_axis=-2)


# k of one K block (128: block_k is min(512, k)), of several (1024 = 2 x
# 512), and n that no block of 256 divides (384 = 3 x 128, the ragged head
# 92,544 = 723 x 128 scaled down; 200, no multiple of 128 either)
STACKED = [(128, 256), (1024, 384), (256, 200)]


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("k,n", STACKED, ids=lambda v: str(v))
def test_int8_matmul_reads_a_layer_in_the_stack(k, n, layer):
    """The stacked form (scalar-prefetched layer index, the weight block
    addressed in the stack viewed (L*k, n)) gives the rank-2 call's result
    on the sliced layer to the last bit, traced index and all, and the
    reference's math."""
    x = jnp.asarray(_w((5, k), seed=21))
    w = _stack(3, k, n)
    got = jax.jit(lambda x, w, l: int8_matmul(x, w, l))(
        x, w, jnp.int32(layer)
    )
    sliced = Int8Param(q=w.q[layer], scale=w.scale[layer])
    assert got.shape == (5, n)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(int8_matmul(x, sliced))
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(int8_matmul_reference(x, sliced)),
        rtol=2e-5, atol=1e-4,
    )


@pytest.mark.parametrize(
    "k,stacked_call", [(128, True), (1024, True), (64, False), (640, False)],
)
def test_int8_matmul_stack_form_follows_k(k, stacked_call):
    """What decides the call's form is the stack's shape: k in whole K
    blocks takes the prefetch form on the stack viewed (L*k, n), no slice
    made; a k that is no whole number of K blocks (toy widths under the
    128-lane floor, 640 under a block of 512: rows past k would be the next
    layer's) is sliced and padded as a rank-2 weight, and gives the same
    numbers as that call."""
    x = jnp.asarray(_w((8, k), seed=22))
    w = _stack(2, k, 128)
    layer = jnp.int32(1)
    (ops,) = pallas_operands(lambda x, w, l: int8_matmul(x, w, l), x, w, layer)
    if stacked_call:
        assert ops[0] == ("int32", (1,)) and ops[2] == ("int8", (2 * k, 128))
        assert ops[3] == ("float32", (2, 1, 128))
    else:
        assert [o[0] for o in ops] == ["float32", "int8", "float32"]
        assert len(ops[1][1]) == 2
    sliced = Int8Param(q=w.q[1], scale=w.scale[1])
    np.testing.assert_array_equal(
        np.asarray(int8_matmul(x, w, layer)),
        np.asarray(int8_matmul(x, sliced)),
    )


@pytest.mark.parametrize("n", [384, 200, 96])
def test_int8_matmul_ragged_n_makes_no_copy_of_the_weight(n):
    """A ragged N hangs the last column block over the edge: the weight
    and its scales reach the kernel as they are (no ``pad`` in the jaxpr,
    the kernel's operands are the arguments' own shapes) and the result is
    allocated (m, n)."""
    x = jnp.asarray(_w((8, 128), seed=23))
    w = quantize_int8(_w((128, n), seed=24))
    fn = lambda x, w: int8_matmul(x, w)  # noqa: E731
    assert "pad" not in str(jax.make_jaxpr(fn)(x, w))
    (ops,) = pallas_operands(fn, x, w)
    assert ops == [
        ("float32", (8, 128)), ("int8", (128, n)), ("float32", (1, n))
    ]
    got = np.asarray(fn(x, w))
    np.testing.assert_allclose(
        got, np.asarray(int8_matmul_reference(x, w)), rtol=2e-5, atol=1e-4
    )
    # bit-equal to the product on the weight padded to whole blocks, which
    # is how the wrapper fed the kernel before (N tiling is no arithmetic)
    pad = (-n) % 128
    padded = Int8Param(
        q=jnp.pad(w.q, ((0, 0), (0, pad))),
        scale=jnp.pad(w.scale, ((0, 0), (0, pad)), constant_values=1.0),
    )
    np.testing.assert_array_equal(got, np.asarray(fn(x, padded))[:, :n])


def _result_block(fn, *args):
    """``(grid, result block)`` of the one ``pallas_call`` in ``fn``'s
    jaxpr: the result's block is ``(block_m, block_n)`` as the call took
    them, whatever the form of the weight."""
    (call,) = [
        e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
        if e.primitive.name == "pallas_call"
    ]
    mapping = call.params["grid_mapping"]
    block = tuple(d.block_size for d in mapping.block_mappings[-1].block_shape)
    return tuple(mapping.grid), block


# n: 1000 is no multiple of 128; 2944 = 23 x 128 is the chat head's 92,544 =
# 723 x 128 scaled down (no block of 256 or of 2,048 divides it, and the
# rule's block leaves a ragged second one); k of one K tile and of three
@pytest.mark.parametrize("k", [128, 1536])
@pytest.mark.parametrize("n", [1000, 2944])
@pytest.mark.parametrize("m", [1, 8, 64, 300])
def test_int8_matmul_n_block_is_no_arithmetic(m, n, k):
    """A column's sum runs over the same K tiles in the same order
    whatever columns share its block: the result on a (k, n) weight is the
    same to the last bit at N blocks of 128, 256, the rule's own and n
    itself."""
    x = jnp.asarray(_w((m, k), seed=30))
    w = quantize_int8(_w((k, n), seed=31))
    ruled = np.asarray(int8_matmul(x, w))
    np.testing.assert_allclose(
        ruled, np.asarray(int8_matmul_reference(x, w)), rtol=2e-5, atol=1e-4
    )
    for block_n in (128, 256, n):
        np.testing.assert_array_equal(
            ruled, np.asarray(int8_matmul(x, w, block_n=block_n))
        )


# (m, k, n) -> (block_m, block_n)
N_BLOCKS = [
    # the serving cells' (k, n) calls: openPangu's decode products and head
    # (kv_a is one block of 640), Phi's, the chat cell's and the long cell's
    # heads
    ((64, 7680, 19200), (64, 2048)),
    ((64, 7680, 18432), (64, 2048)),
    ((64, 18432, 7680), (64, 2048)),
    ((64, 16384, 7680), (64, 2048)),
    ((64, 7680, 1536), (64, 1536)),
    ((64, 1536, 24576), (64, 2048)),
    ((64, 7680, 576), (64, 640)),
    ((64, 7680, 2048), (64, 2048)),
    ((64, 2560, 200064), (64, 2048)),
    ((32, 2048, 92544), (32, 2048)),
    ((8, 4096, 32000), (8, 2048)),
    # prefill buckets, and the stacked cells' up projections as PR 33 set them
    ((2048, 7680, 18432), (256, 2048)),
    ((1024, 512, 32768), (256, 2048)),
    ((32, 2048, 8192), (32, 2048)),
    ((4096, 4096, 14336), (256, 2048)),
    # a K tile under 512 rows takes more columns for its megabyte, as far as
    # the M tile leaves room in fast memory
    ((64, 256, 5120), (64, 4096)),
    ((2048, 256, 5120), (256, 2048)),
    ((8, 128, 16384), (8, 8192)),
    ((300, 128, 16384), (256, 2048)),
    # a narrow n and a toy width clamp to n in whole lane tiles
    ((8, 512, 200), (8, 256)),
    ((5, 64, 96), (8, 128)),
]


@pytest.mark.parametrize("shape,blocks", N_BLOCKS, ids=lambda v: str(v))
def test_int8_matmul_n_block_rule(shape, blocks):
    """The rule itself, on the shapes the cells call: about 1 MB of int8 a
    grid step (2,048 columns at the K tile of 512), inside fast memory,
    clamped to n; and one rule: a (k, n) weight and a stack of them take
    the same blocks for the same shapes. Shapes alone: nothing runs."""
    m, k, n = shape
    x = jax.ShapeDtypeStruct((m, k), jnp.float32)
    flat = Int8Param(
        q=jax.ShapeDtypeStruct((k, n), jnp.int8),
        scale=jax.ShapeDtypeStruct((1, n), jnp.float32),
    )
    stack = Int8Param(
        q=jax.ShapeDtypeStruct((2, k, n), jnp.int8),
        scale=jax.ShapeDtypeStruct((2, 1, n), jnp.float32),
    )
    grid, got = _result_block(lambda x, w: int8_matmul(x, w), x, flat)
    assert got == blocks
    assert grid[1] == -(-n // blocks[1])
    layer = jax.ShapeDtypeStruct((), jnp.int32)
    assert _result_block(
        lambda x, w, l: int8_matmul(x, w, l), x, stack, layer
    ) == (grid, got)

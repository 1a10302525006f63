"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached on the CPU test mesh, but the TPU compiler is
installed: ``jax.experimental.topologies`` describes a ``v5e:2x2`` host
and ``jit(...).lower(shapes).compile()`` raises whatever the chip's
compiler would raise (block shapes the Mosaic lowering refuses, scoped
VMEM overflow) — faults interpret mode cannot see. Every case runs
``interpret=False`` at the ``1b`` preset's widths (vocab 32000, d_model
2048, 16 heads of 128, d_ff 8192 — ``examples/serve_llm_int8.py``).

A compile that passes is not a chip run: nothing here produces a result
or a time. The topology is described inside a module-scoped fixture (one
pytest-xdist worker loads the TPU library, the rest never touch it) and
every compile happens in the test's own process — keep all such cases in
this one file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_tutorials_tpu.ops.flash_attention import (
    flash_attention,
)
from pytorch_distributed_training_tutorials_tpu.ops.fused_loss import (
    fused_cross_entropy,
    fused_cross_entropy_tp,
)
from pytorch_distributed_training_tutorials_tpu.ops.fused_optim import (
    fused_adamw,
)
from pytorch_distributed_training_tutorials_tpu.ops.paged_attention import (
    paged_attention,
)
from pytorch_distributed_training_tutorials_tpu.ops.quant import (
    Int8Param,
    int8_matmul,
    int8_matmul_tp,
)

VOCAB, D_MODEL, N_HEADS, HEAD_DIM, D_FF = 32000, 2048, 16, 128, 8192
BATCH, SEQ = 4, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp_mesh(topo):
    """The four described chips as ``{'data': 2, 'model': 2}``."""
    import numpy as np

    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))


def _compile(fn, one_chip, *shapes):
    """Lower ``fn`` on shape structs placed on the described chip and run
    the TPU compiler; returns the optimized HLO text."""
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes,
    )
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, "no Mosaic kernel in the compiled HLO"
    return hlo


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(one_chip, grad):
    qkv = _sds((BATCH, SEQ, N_HEADS, HEAD_DIM), jnp.bfloat16)
    fa = functools.partial(
        flash_attention, block_q=512, block_k=512, interpret=False
    )
    if grad:
        fn = jax.grad(
            lambda q, k, v: fa(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )
    else:
        fn = fa
    _compile(fn, one_chip, qkv, qkv, qkv)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_fused_cross_entropy_compiles(one_chip, grad):
    """(8192 x 2048) . (2048 x 32000): the backward's dh/dW calls hold a
    hidden block, a head block, an output block and an f32 accumulator —
    over the 16 MB scoped-VMEM limit at 512-row blocks and d = 2048."""
    hidden = _sds((BATCH, SEQ, D_MODEL), jnp.bfloat16)
    head = _sds((D_MODEL, VOCAB), jnp.bfloat16)
    targets = _sds((BATCH, SEQ), jnp.int32)

    def loss(h, w, y):
        return fused_cross_entropy(h, w, y, interpret=False).mean()

    fn = jax.grad(loss, argnums=(0, 1)) if grad else loss
    _compile(fn, one_chip, hidden, head, targets)


def test_fused_adamw_compiles(one_chip):
    params = {
        "ffn": _sds((D_MODEL, D_FF), jnp.float32),
        "embed": _sds((VOCAB, D_MODEL), jnp.float32),
        "norm": _sds((D_MODEL,), jnp.float32),
    }
    tx = fused_adamw(1e-3, interpret=False)

    def step(p, g):
        return tx.update(g, tx.init(p), p)

    _compile(step, one_chip, params, params)


@pytest.mark.parametrize(
    "m,k,n",
    [
        (8, D_MODEL, D_FF),
        (256, D_MODEL, D_FF),
        (8, D_FF, D_MODEL),
        (8, D_MODEL, VOCAB),
    ],
    ids=["decode_up", "prefill_up", "decode_down", "decode_head"],
)
def test_int8_matmul_compiles(one_chip, m, k, n):
    x = _sds((m, k), jnp.bfloat16)
    w = Int8Param(q=_sds((k, n), jnp.int8), scale=_sds((1, n), jnp.float32))
    _compile(
        lambda x, w: int8_matmul(x, w, interpret=False), one_chip, x, w
    )


@pytest.mark.parametrize("s", [1, 16], ids=["decode", "chunk16"])
@pytest.mark.parametrize("quant", [None, "int8", "int4"],
                         ids=["bf16", "int8kv", "int4kv"])
@pytest.mark.parametrize("kv", [N_HEADS, 4], ids=["mha", "gqa4"])
def test_paged_attention_compiles(one_chip, kv, quant, s):
    """H 16, D 128 over KV 16 (grp 1) and KV 4 (grp 4): the decode step
    (S = 1) and the chunked continuation (S > 1), every KV storage."""
    b, pages, page_size, p_cap = 8, 64, 64, 8
    q = _sds((b, s, N_HEADS, HEAD_DIM), jnp.bfloat16)
    d_store = HEAD_DIM // 2 if quant == "int4" else HEAD_DIM
    pool_dtype = {None: jnp.bfloat16, "int8": jnp.int8, "int4": jnp.uint8}
    pool = _sds((pages, page_size, kv, d_store), pool_dtype[quant])
    table = _sds((b, p_cap), jnp.int32)
    pos = _sds((b,), jnp.int32)
    if quant is None:
        fn = functools.partial(paged_attention, interpret=False)
        _compile(fn, one_chip, q, pool, pool, table, pos)
        return
    scale_dtype = jnp.float32 if quant == "int8" else jnp.bfloat16
    scale = _sds((pages, page_size, kv), scale_dtype)

    def fn(q, kp, vp, tbl, pos, ks, vs):
        return paged_attention(
            q, kp, vp, tbl, pos, k_scale=ks, v_scale=vs, quant=quant,
            interpret=False,
        )

    _compile(fn, one_chip, q, pool, pool, table, pos, scale, scale)


def _on(mesh, shape, dtype, *spec):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P(*spec))
    )


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_fused_cross_entropy_tp_compiles_on_a_mesh(tp_mesh, grad):
    """The vocab-split head under ``shard_map`` on four described chips —
    where "Mosaic kernels cannot be automatically partitioned" shows for a
    bare ``pallas_call`` under a multi-device mesh."""
    hidden = _on(tp_mesh, (BATCH, SEQ, D_MODEL), jnp.bfloat16, "data")
    head = _on(tp_mesh, (D_MODEL, VOCAB), jnp.bfloat16, None, "model")
    targets = _on(tp_mesh, (BATCH, SEQ), jnp.int32, "data")

    def loss(h, w, y):
        return fused_cross_entropy_tp(
            h, w, y, tp_mesh, interpret=False
        ).mean()

    fn = jax.grad(loss, argnums=(0, 1)) if grad else loss
    hlo = jax.jit(fn).lower(hidden, head, targets).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo  # the cross-shard logsumexp / dh psum


@pytest.mark.parametrize("kind", ["column", "row"])
def test_int8_matmul_tp_compiles_on_a_mesh(tp_mesh, kind, monkeypatch):
    """Megatron column (up-projection) and row (down-projection) splits of
    the int8 kernel at the decode shape. ``int8_matmul_tp`` has no
    ``interpret`` argument; its kernel asks ``jax.default_backend()``, which
    still says cpu here, so the test answers for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    k, n = (D_MODEL, D_FF) if kind == "column" else (D_FF, D_MODEL)
    w_spec = (None, "model") if kind == "column" else ("model", None)
    s_spec = (None, "model") if kind == "column" else (None, None)
    x_spec = ("data", None) if kind == "column" else ("data", "model")
    x = _on(tp_mesh, (8, k), jnp.bfloat16, *x_spec)
    w = Int8Param(
        q=_on(tp_mesh, (k, n), jnp.int8, *w_spec),
        scale=_on(tp_mesh, (1, n), jnp.float32, *s_spec),
    )
    hlo = (
        jax.jit(lambda x, w: int8_matmul_tp(x, w, tp_mesh, kind=kind))
        .lower(x, w).compile().as_text()
    )
    assert "tpu_custom_call" in hlo

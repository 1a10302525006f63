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
import hashlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_tutorials_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_forward,
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
from pytorch_distributed_training_tutorials_tpu.ops.decode_attention import (
    decode_attention,
)
from pytorch_distributed_training_tutorials_tpu.ops.latent_attention import (
    latent_decode_attention,
)
from pytorch_distributed_training_tutorials_tpu.ops.selective_scan import (
    selective_scan,
)
from pytorch_distributed_training_tutorials_tpu.ops.quant import (
    Int8Param,
    grouped_int8_matmul,
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


@pytest.mark.parametrize(
    "s,h,kv,d,dtype",
    [
        (4096, 32, 8, 128, jnp.float32),  # the long cell's largest bucket
        (1024, 16, 8, 128, jnp.float32),  # the chat cell's, one tile
        (2048, 20, 4, 128, jnp.bfloat16),  # the Falcon-H1 cell's
        (1536, 8, 2, 256, jnp.bfloat16),  # a padded tail, a wider head
    ],
    ids=["long", "chat", "falcon_h1", "ragged_d256"],
)
def test_flash_attention_forward_compiles(one_chip, s, h, kv, d, dtype):
    """The forward alone at its tiles of 1,024 x 1,024 with K and V at
    their stored head count: the kernel's K and V operands keep ``kv``
    heads, float32 operands arrive rounded to bfloat16, the result is in
    ``q``'s dtype."""
    hlo = _compile(
        functools.partial(flash_attention_forward, interpret=False),
        one_chip, _sds((1, s, h, d), dtype), _sds((1, s, kv, d), dtype),
        _sds((1, s, kv, d), dtype),
    )
    sp = -(-s // 1024) * 1024
    out = "f32" if dtype == jnp.float32 else "bf16"
    assert re.search(
        rf"%flash_attention_fwd[\w.]* = \({out}\[{h},{sp},{d}\]\S*, .*?"
        rf"operand_layout_constraints=\{{bf16\[{h},{sp},{d}\]\{{2,1,0\}}, "
        rf"bf16\[{kv},{sp},{d}\]\{{2,1,0\}}, bf16\[{kv},{sp},{d}\]", hlo
    ), "the kernel's operands are not (h, kv, kv) heads of bfloat16"


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
        (32, D_MODEL, 92544),  # 723 x 128: no block of 256 divides it
        (8, D_MODEL, 1000),  # no multiple of 128 either
        # openPangu-Ultra-MoE's unrolled layers at 64 slots, and a prefill
        (64, 16384, 7680),
        (64, 7680, 18432),
        (64, 18432, 7680),
        (64, 7680, 576),  # one block of 640
        (64, 7680, 19200),
        (2048, 7680, 18432),
        (64, 2560, 200064),
        (8, 4096, 32000),
    ],
    ids=["decode_up", "prefill_up", "decode_down", "decode_head",
         "chat_head", "ragged_head", "pangu_o", "pangu_up", "pangu_down",
         "pangu_kv_a", "pangu_head", "pangu_prefill_up", "phi_head",
         "long_head"],
)
def test_int8_matmul_compiles(one_chip, m, k, n):
    """A (k, n) weight: three operands, x first (the call of every
    unrolled layer and of the head). A ragged n is no reason for a copy:
    the weight reaches the kernel as the argument it is (before ISSUE 33
    the chat cell's 190 MB head was padded to 92,672 columns on every
    chain). The N block is the call's own (ISSUE 35: 1 MB of int8 a grid
    step): one too large for fast memory at a cell's shape fails here."""
    x = _sds((m, k), jnp.bfloat16)
    w = Int8Param(q=_sds((k, n), jnp.int8), scale=_sds((1, n), jnp.float32))
    hlo = _compile(
        lambda x, w: int8_matmul(x, w, interpret=False), one_chip, x, w
    )
    call, = re.findall(r"= f32\[[\d,]+\]\S* custom-call\(([^)]*)\)", hlo)
    assert len(call.split(",")) == 3  # no s32[1] layer index
    assert " pad(" not in hlo
    assert f"f32[{m},{n}]" in hlo  # the result is allocated (m, n)


# (layers, slots or bucket, k, n): the chat and long cells' up and down
# projections, a decode step and a prefill bucket
STACKS = {
    "chat_up": (24, 32, 2048, 8192),
    "chat_down": (24, 32, 8192, 2048),
    "long_up": (32, 8, 4096, 14336),
    "long_down_prefill": (32, 2048, 14336, 4096),
}


@pytest.mark.parametrize("case", sorted(STACKS))
def test_int8_matmul_reads_the_stack_in_place(one_chip, case):
    """The stacked form at the cells' widths: the (L, k, n) stack reaches
    the kernel viewed (L*k, n), which the chip's compiler takes as a
    bitcast (k is a whole number of its 32-row int8 tiles); nothing is
    sliced, copied or allocated beside the result."""
    layers, m, k, n = STACKS[case]
    compiled = jax.jit(
        lambda x, q, s, l: int8_matmul(
            x, Int8Param(q=q, scale=s), l, interpret=False)
    ).lower(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in [
            ((m, k), jnp.float32), ((layers, k, n), jnp.int8),
            ((layers, 1, n), jnp.float32), ((), jnp.int32),
        ]
    ]).compile()
    hlo = compiled.as_text()
    assert re.search(
        rf"%int8_matmul[\w.]* = f32\[{m},{n}\]\S* custom-call\(", hlo)
    assert f"s32[1]{{0}}, f32[{m},{k}]{{1,0}}, s8[{layers * k},{n}]{{1,0}}" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    assert _s8_made(hlo) == []


def _s8_made(hlo: str) -> list:
    """(opcode, dims) of every instruction of an optimized HLO text that
    MAKES one layer's int8 weight: an ``s8`` result of rank 2, or of rank 3
    with a leading 1 (a layer's slice, a copy, the padded head, or the
    fusion of one: ``dynamic-slice_bitcast_fusion`` was the scan's).
    Parameters, tuple plumbing and bitcasts make nothing, and what the
    compiler's own prefetch moves into fast memory ahead of its use
    (``S(1)``: ``copy-start`` / ``copy-done`` of a weight small enough, at
    toy sizes; ``slice-start`` of six layers of a whole stack at a time,
    rank 3, once a launch) is not the program's copy; an int8 or int4
    cache's rows are rank 4."""
    quiet = ("parameter", "get-tuple-element", "bitcast", "copy-done")
    return [
        (op, dims)
        for dims, op in re.findall(
            r"= s8\[((?:1,)?\d+,\d+)\]\S* ([\w\-]+)\(", hlo)
        if op not in quiet
    ]


@pytest.mark.parametrize(
    "rows,block_m,k,n",
    [
        (768, 16, 7680, 2048),  # 64 slots x 8 choices + 16 tiles of padding
        (768, 16, 2048, 7680),
        (18432, 128, 7680, 2048),  # a 2,048-token prefill, the worst case
        (18432, 128, 2048, 7680),
    ],
    ids=["decode_up", "decode_down", "prefill_up", "prefill_down"],
)
def test_grouped_int8_matmul_compiles(one_chip, rows, block_m, k, n):
    """16 held experts at openPangu-Ultra-MoE's widths (ISSUE 30): the
    grid's leading bound is a traced scalar."""
    _compile(
        lambda x, q, s, te, nt: grouped_int8_matmul(
            x, q, s, te, nt, block_m=block_m, interpret=False),
        one_chip, _sds((rows, k), jnp.bfloat16), _sds((16, k, n), jnp.int8),
        _sds((16, 1, n), jnp.float32), _sds((rows // block_m,), jnp.int32),
        _sds((), jnp.int32),
    )


@pytest.mark.parametrize(
    "layers, lowered",
    [
        (6, "50abd54332bc70b78350f62911516ff475699fd70a2bcf71c7e617a4547102cb"),
        (1, "8b2a3a02f8fd2f7b1d12db030f66637aba6bd0b2a56a83cc81c6b6f5d7210711"),
    ],
    ids=["stack", "one_layer"],
)
def test_latent_decode_attention_compiles(one_chip, layers, lowered):
    """128 heads over rows of 640 (512 + 64, five lane tiles), 64 slots x
    4,096 positions, read in the carried stack at a traced layer index.
    The lowered program (its Mosaic payload included, without source
    locations) is pinned: ``decode_attention`` walks its own schedule and
    leaves ``block_bounds``, which this kernel shares, as it was."""
    shapes = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in [
            ((64, 128, 640), jnp.bfloat16),
            ((layers, 64, 4096, 640), jnp.bfloat16), ((), jnp.int32),
            ((64,), jnp.int32),
        ]
    ]

    def kernel():  # a function of its own each time: nothing traced is reused
        return functools.partial(
            latent_decode_attention, sm_scale=192 ** -0.5, interpret=False)

    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = jax.jit(kernel()).lower(*shapes).as_text(debug_info=False)
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    assert hashlib.sha256(text.encode()).hexdigest() == lowered
    _compile(kernel(), one_chip, *shapes)


@pytest.mark.parametrize(
    "stack, heads, dtype, heads_major",
    [
        ((24, 32, 2048, 8, 128), 16, jnp.bfloat16, False),
        ((32, 8, 4096, 8, 128), 32, jnp.bfloat16, False),
        ((16, 8, 512, 16, 128), 16, jnp.float32, False),
        ((1, 64, 10, 4096, 128), 40, jnp.bfloat16, True),
        ((6, 64, 4, 4096, 128), 20, jnp.bfloat16, True),
        ((2, 256, 1024, 8, 128), 64, jnp.bfloat16, False),
    ],
    ids=["chat", "long", "1b_mha_f32", "phi_shared", "falcon_h1",
         "256_slots_64_heads"],
)
def test_decode_attention_compiles(one_chip, stack, heads, dtype, heads_major):
    """The chat and long cells' carried K and V stacks in bfloat16, read at
    a traced layer index in blocks stored ``(512, 8, 128)`` (and the ``1b``
    preset's float32 cache of 16 heads, ``chip_smoke.py``'s engine, in
    blocks of 128 rows; the Phi cell's shared cache, 40 heads over 10
    pairs, and the Falcon-H1 cell's stack, 20 over 4, a KV head's rows
    together; and 256 slots of 64 heads, whose q and result pass the
    fast memory a kernel is granted unasked): the call takes the stacks as
    they lie in HBM and walks their live blocks itself (no copy or change
    of layout of either, which would be 3.2 GB), and allocates nothing
    beside its result."""
    fn = jax.jit(
        lambda q, k, v, layer, pos: decode_attention(
            q, k, v, layer, pos, heads_major=heads_major, interpret=False)
    )
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in [
            ((stack[1], heads, stack[4]), jnp.float32),
            (stack, dtype), (stack, dtype),
            ((), jnp.int32), ((stack[1],), jnp.int32),
        ]
    ]
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "decode_attention" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    dims = ",".join(str(n) for n in stack)
    assert not re.findall(rf"= \w+\[{dims}\]\S* (?:copy|fusion)\(", hlo)


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


CHAT = dict(  # benchmark/configs/internlm2-1.8b.json, serve mode
    vocab_size=92544, d_model=2048, n_layers=24, n_heads=16, n_kv_heads=8,
    d_ff=8192, max_seq_len=2048, rope_theta=1e6, norm_eps=1e-5,
    dtype=jnp.float32, scan_layers=True, kv_cache_dtype=jnp.bfloat16,
)
CHAT_SLOTS = 32


def _int8_engine_of_shapes(monkeypatch, cfg: dict, n_slots: int, **options):
    """``ServeEngine`` over the int8 form of a float model at ``cfg``, with
    parameters and slot state as shapes: nothing is allocated at that size.
    Returns the engine and its parameters."""
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
        quantize_lm_params,
    )
    from pytorch_distributed_training_tutorials_tpu.serve import ServeEngine
    from pytorch_distributed_training_tutorials_tpu.serve import (
        engine as engine_module,
    )
    from pytorch_distributed_training_tutorials_tpu.serve.slots import (
        init_slot_state,
    )

    # int8_matmul asks the backend whether to interpret its kernel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the engine's own slot state, as shapes
    monkeypatch.setattr(
        engine_module, "init_slot_state",
        lambda model, params, *a, **kw: jax.eval_shape(
            lambda p: init_slot_state(model, p, *a, **kw), params
        ),
    )
    float_model = TransformerLM(TransformerConfig(**cfg))
    model = TransformerLM(TransformerConfig(**cfg, quantized=True))
    params = jax.eval_shape(
        lambda key: quantize_lm_params(
            float_model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        ),
        jax.random.PRNGKey(0),
    )
    return ServeEngine(
        model, params, n_slots=n_slots, tokens_per_launch=8, **options
    ), params


def _update_operands(hlo: str) -> dict[str, int]:
    """Shape -> element count of the update operand of every
    ``dynamic-update-slice`` in an optimized HLO text (operands are named,
    not typed, there: each is looked up among its own computation's
    instructions)."""
    out = {}
    for comp in hlo.split("\n\n"):
        shapes = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", comp))
        for update in re.findall(
            r" dynamic-update-slice\(%[\w.\-]+, %([\w.\-]+)", comp
        ):
            dims = re.findall(r"\d+", shapes[update].split("[")[1])
            out[shapes[update]] = math.prod(int(d) for d in dims)
    return out


@pytest.mark.parametrize(
    "options, program, leaf, temp_share",
    [
        ({}, "_chain_fn", "cached_key", 1 / 24),  # < K + V of one layer
        ({"kv_bits": 8}, "_chain_fn", "cached_key", 1 / 8),
        # the compiler changes the layout of the whole u8[..., 8, 64] stack
        # at the program's entry and back at its exit: four copies a
        # launch (six, and 6.7 times the cache, when the scan ran over it)
        ({"kv_bits": 4}, "_chain_fn", "cached_key", 2.5),
        (dict(paged=True, page_size=128, pool_pages=512), "_chain_fn",
         "paged_key", 1 / 8),
        (dict(paged=True, page_size=128, pool_pages=512, paged_kernel=True),
         "_chain_fn", "paged_key", 1 / 8),
        ({"speculative_k": 3}, "_spec_chain_fn", "cached_key", 1 / 24),
    ],
    ids=["bf16", "int8", "int4", "paged", "paged_kernel", "speculative"],
)
def test_serve_chain_carries_the_cache_in_place(
    one_chip, monkeypatch, options, program, leaf, temp_share
):
    """``ServeEngine``'s decode chain at the chat cell's widths (24 layers,
    32 slots x 2048 positions, 8 KV heads of 128, int8 weights), over the
    cache's storages (bf16 as the cell runs it, int8 and int4 with their
    scales, the paged pool of the same bytes read by gather and by the
    kernel) and the speculative chain: the layer scan carries the stacked
    cache, so the compiled program writes a layer's new rows into it in
    place. Scanning over the cache instead copies every layer's whole
    slice back into the stack on every decode step
    (``bitcast_dynamic-update-slice_fusion`` with a ``bf16[32,2048,8,128]``
    update, half of the chat cell's device time before ISSUE 28): this is
    the test that fails if that comes back. The ``bf16`` case, the chat
    cell's own, also holds the read side (ISSUE 31): the other storages,
    the paged pool and the speculative chain keep the plain path and its
    read copy. Nothing is allocated at that size: params and the engine's
    slot state are shapes."""
    engine, params = _int8_engine_of_shapes(
        monkeypatch, CHAT, CHAT_SLOTS, **options
    )
    state = engine._state
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (params, state),
    )
    compiled = (
        jax.jit(getattr(engine, program), donate_argnums=(1,))
        .lower(*placed).compile()
    )
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo  # the int8 kernels, not their emulation
    # the weights are read where they lie (ISSUE 33): no layer's int8
    # slice, no copy, no padded head is made in any of these programs
    assert _s8_made(hlo) == []

    attn = state["cache"]["layers"]["block"]["attn"]
    kv = attn[leaf]
    assert kv.shape[0] == 24 and kv.size // kv.shape[-1] == 24 * 32 * 2048 * 8
    # the smallest layer slice of a stacked leaf that holds rows of
    # positions (K, V, their scales); cache_index and page_table are a
    # number a slot or a page
    layer_slice = min(
        v.size // v.shape[0] for v in attn.values() if v.ndim >= 4
    )
    sizes = _update_operands(hlo)
    assert sizes  # the reader still finds the instruction it looks for
    assert all(n < layer_slice for n in sizes.values()), sizes
    # the state is donated into the result: K and V are not allocated twice
    cache_bytes = sum(
        v.size * v.dtype.itemsize for v in attn.values() if v.ndim >= 4
    )
    analysis = compiled.memory_analysis()
    assert analysis.alias_size_in_bytes >= cache_bytes
    # nor copied whole inside (half of it is all of K)
    assert analysis.temp_size_in_bytes < temp_share * cache_bytes
    if options:
        return
    # the cell's own storage: the step's attention is the kernel, which
    # reads K and V in the carried stack. Nothing in the chain yields a
    # layer's slice of either (the read copy and its change of layout,
    # half of the cell's device time before ISSUE 31): every bfloat16
    # result of that size or more is a whole stack, updated in place.
    # Nothing else is left of ``temp_size_in_bytes`` (190 MB before ISSUE
    # 33: the int8 head padded to s8[2048, 92672]).
    assert analysis.temp_size_in_bytes < 1 << 20
    assert re.search(
        r"%decode_attention[\w.]* = f32\[32,16,128\]\S* custom-call\(", hlo
    ), "no decode_attention kernel in the chain"
    big = [
        (op, dims) for dims, op in re.findall(
            r"= bf16\[([\d,]+)\]\S* ([\w\-]+)\(", hlo
        )
        if math.prod(int(n) for n in dims.split(",")) >= layer_slice
    ]
    assert big and all(
        dims == "24,32,2048,8,128" and op not in ("copy", "dynamic-slice")
        for op, dims in big
    ), big


TOY = dict(  # every k a whole K block; a head of 9 x 128 columns, ragged
    vocab_size=1152, d_model=256, n_layers=3, n_heads=2, d_ff=512,
    max_seq_len=256, dtype=jnp.float32, kv_cache_dtype=jnp.bfloat16,
    quantized=True,
)


@pytest.mark.parametrize(
    "scan_layers, program",
    [(True, "_chain_fn"), (True, "_prefill_fn"), (False, "_chain_fn")],
    ids=["scan_chain", "scan_prefill", "unrolled_chain"],
)
def test_int8_programs_copy_no_weight(
    one_chip, monkeypatch, scan_layers, program
):
    """A quantized toy model through ``ServeEngine``: under the layer scan
    the chain (which carries its cache) and the prefill (which creates it)
    feed every layer's ``int8_matmul`` the stack and the ``s32[1]`` layer
    index, the head the (k, n) weight as it is, ragged; no program makes a
    layer's int8 weight (slice, copy or pad). The same model unrolled
    compiles to the (k, n) calls alone: no layer index anywhere."""
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.serve import ServeEngine
    from pytorch_distributed_training_tutorials_tpu.serve import (
        engine as engine_module,
    )
    from pytorch_distributed_training_tutorials_tpu.serve.slots import (
        init_slot_state,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        engine_module, "init_slot_state",
        lambda model, params, *a, **kw: jax.eval_shape(
            lambda p: init_slot_state(model, p, *a, **kw), params
        ),
    )
    model = TransformerLM(TransformerConfig(**TOY, scan_layers=scan_layers))
    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    engine = ServeEngine(model, params, n_slots=4, tokens_per_launch=4)
    args = (params, engine._state)
    if program == "_prefill_fn":
        i32 = _sds((), jnp.int32)
        args += (_sds((1, 64), jnp.int32), i32, i32, i32, i32)
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        args,
    )
    hlo = (
        jax.jit(getattr(engine, program), donate_argnums=(1,))
        .lower(*placed).compile().as_text()
    )
    calls = re.findall(
        r"%int8_matmul[\w.]* = f32\[[\d,]+\]\S* custom-call\(.*?"
        r"operand_layout_constraints=\{(.*?)\}, frontend_attributes", hlo)
    stacked = [c for c in calls if c.startswith("s32[1]{0}, ")]
    assert len(calls) - len(stacked) == (1 if scan_layers else 1 + 7 * 3)
    assert len(stacked) == (7 if scan_layers else 0)
    assert all(re.search(r", s8\[(768|1536),\d+\]\{1,0\}, f32\[3,1,", c)
               for c in stacked), stacked
    assert any(", s8[256,1152]{1,0}, f32[1,1152]" in c for c in calls)
    assert _s8_made(hlo) == []


@pytest.mark.parametrize("positions", [2048, 600], ids=["bucket", "ragged"])
def test_selective_scan_compiles(one_chip, positions):
    """One Mamba layer's scan at Phi-4-mini-flash-reasoning's sizes (5,120
    channels, 16 states): a prompt bucket of 2,048, and ``generate()``'s
    600 positions, padded to whole blocks with ``delta = 0``."""
    hlo = _compile(
        lambda u, d, a, b, c: selective_scan(u, d, a, b, c, interpret=False),
        one_chip,
        _sds((1, positions, 5120), jnp.float32),
        _sds((1, positions, 5120), jnp.float32), _sds((16, 5120), jnp.float32),
        _sds((1, positions, 16), jnp.float32),
        _sds((1, positions, 16), jnp.float32),
    )
    assert re.search(r"%selective_scan[\w.]* = ", hlo)


@pytest.mark.parametrize("program", ["_chain_fn", "_prefill_fn"])
def test_recurrent_state_programs_copy_no_cache(one_chip, monkeypatch, program):
    """``ServeEngine``'s chain and its 2,048 prefill for the layout
    ``mb_per_layer`` gives, at Phi-4-mini-flash-reasoning's published widths
    (32 layers, 64 slots x 4,096 positions, int8): nine Mamba states, eight
    rings of 512 rows and the one shared cache ride the two layer scans as
    carries, ``decode_attention`` reads the rings and the shared cache
    where they lie (a KV pair's rows together: with the ten pairs innermost
    the compiler relaid both stacks whole, twice a launch, 5.3 GB of
    temporaries), every scanned product reads the stacked int8 weights at
    its layer index, and no stack of the cache, no int8 weight and no
    embedding table is copied (ISSUE 34). Shapes only."""
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.serve import ServeEngine
    from pytorch_distributed_training_tutorials_tpu.serve import (
        engine as engine_module,
    )
    from pytorch_distributed_training_tutorials_tpu.serve.slots import (
        init_slot_state,
        tree_nbytes,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        engine_module, "init_slot_state",
        lambda model, params, *a, **kw: jax.eval_shape(
            lambda p: init_slot_state(model, p, *a, **kw), params
        ),
    )
    model = TransformerLM(TransformerConfig(
        vocab_size=200064, d_model=2560, n_layers=32, n_heads=40,
        n_kv_heads=20, d_ff=10240, max_seq_len=4096, norm_eps=1e-5,
        mb_per_layer=2, sliding_window=512, tie_embeddings=True,
        scan_layers=True, quantized=True, dtype=jnp.bfloat16,
        kv_cache_dtype=jnp.bfloat16,
    ))
    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    engine = ServeEngine(model, params, n_slots=64, tokens_per_launch=8)
    cache = engine._state["cache"]
    assert engine.stats("slot") == {
        "slot_kv_bytes": 4096 * 5120, "slot_ring_bytes": 8 * 512 * 5120,
        "slot_state_bytes": 9 * 5120 * (16 + 3) * 4,
    }
    args = (params, engine._state)
    if program == "_prefill_fn":
        i32 = _sds((), jnp.int32)
        args += (_sds((1, 2048), jnp.int32), i32, i32, i32, i32)
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        args,
    )
    compiled = (
        jax.jit(getattr(engine, program), donate_argnums=(1,))
        .lower(*placed).compile()
    )
    hlo = compiled.as_text()
    analysis = compiled.memory_analysis()
    # the slot tree is donated into the result, and nothing the size of the
    # shared cache's K (0.34 GB) is made beside it: the chain's temporaries
    # are 8 MB; the prefill's are a bucket's activations
    assert analysis.alias_size_in_bytes >= tree_nbytes(cache)
    shared_k = tree_nbytes(cache["shared_key"])
    limit = (1 << 24) if program == "_chain_fn" else 2 * shared_k
    assert analysis.temp_size_in_bytes < limit, analysis.temp_size_in_bytes
    # no layer's int8 weight is made: what is left are the 160 rows of
    # ``dt_proj`` (no whole K tile: sliced and padded, 1.3 MB), ``x_proj``'s
    # stack of 192 columns (no whole lane tiles: 7.9 MB relaid once a
    # prefill) and what the compiler itself moves into fast memory ahead of
    # its use (``S(1)``)
    made = [
        (op, dims) for dims, layout, op in re.findall(
            r"= s8\[([\d,]+)\](\S*) ([\w\-]+)\(", hlo)
        if op not in ("parameter", "get-tuple-element", "bitcast", "copy-done")
        and "S(1)" not in layout
        and math.prod(int(n) for n in dims.split(",")) >= 1 << 23
    ]
    assert made == [], made
    # every bfloat16 result the size of a stack IS a stack, updated in place
    big = {
        (op, dims) for dims, op in re.findall(
            r"= bf16\[([\d,]+)\]\S* ([\w\-]+)\(", hlo)
        if math.prod(int(n) for n in dims.split(",")) * 2 >= shared_k
        and op not in ("parameter", "get-tuple-element", "bitcast", "while",
                       "tuple")
    }
    stacks = {"1,64,10,4096,128", "64,10,4096,128", "8,64,10,512,128"}
    if program == "_prefill_fn":  # a bucket's fused gate|up, in bfloat16
        stacks |= {"1,2048,20480", "2048,20480"}
    assert big and all(
        dims in stacks and op not in ("copy", "dynamic-slice", "transpose")
        for op, dims in big
    ), big
    assert "200064,2560" not in "".join(
        line for line in hlo.splitlines()
        if " convert(" in line or " copy(" in line)
    if program == "_prefill_fn":
        # a Mamba layer's scan is one kernel: a period's (the compiler may
        # peel the layer scan's first turn) and layer 16's
        assert len(set(re.findall(r"%(selective_scan[\w.]*) = ", hlo))) >= 2
    if program == "_chain_fn":
        # the rings, layer 17 and the cross layers: three call sites
        assert len(set(re.findall(
            r"%(decode_attention[\w.]*) = bf16\[64,40,128\]", hlo))) == 3
    stacked = re.findall(
        r"%int8_matmul[\w.]* = f32\[[\d,]+\]\S* custom-call\(.*?"
        r"operand_layout_constraints=\{(s32\[1\]\{0\}, .*?)\}, frontend", hlo)
    # 6 + 4 products a period of layers_a, 4 + 4 of layers_b, less dt_proj
    assert len(stacked) == 17, len(stacked)


def test_float_tp_serve_chain_compiles_on_a_mesh(topo, monkeypatch):
    """The chat cell's model in floats, served tensor-parallel over the
    four described chips (16 heads and 8 KV heads of 128 split four ways,
    a window of whole blocks: every width the ``decode_attention`` kernel
    takes): the engine serves the model with the strategy's mesh on its
    config, so the chain's step keeps the head-sharded plain einsums. A
    bare ``pallas_call`` on the stack GSPMD has sharded is what the chip's
    compiler refuses (or feeds by gathering the whole stack). Params and
    slot state are shapes with their shardings."""
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        TP_RULES,
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.parallel import (
        TensorParallel,
    )
    from pytorch_distributed_training_tutorials_tpu.serve import ServeEngine
    from pytorch_distributed_training_tutorials_tpu.serve import (
        engine as engine_module,
    )
    from pytorch_distributed_training_tutorials_tpu.serve.slots import (
        init_slot_state,
    )

    def as_shapes(tree, shardings):
        return jax.tree_util.tree_map(
            lambda leaf, sh: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sh
            ),
            tree, shardings,
        )

    strategy = TensorParallel(
        Mesh(np.array(topo.devices), ("model",)), TP_RULES
    )
    # a kernel that asks the backend whether to interpret would be Mosaic
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        strategy, "shard_state",
        lambda tree: as_shapes(tree, strategy.variable_shardings(tree)),
    )

    def slot_shapes(model, params, *a, strategy=None, **kw):
        state = jax.eval_shape(
            lambda p: init_slot_state(model, p, *a, **kw), params
        )
        return as_shapes(state, strategy.slot_shardings(state))

    monkeypatch.setattr(engine_module, "init_slot_state", slot_shapes)
    model = TransformerLM(TransformerConfig(**CHAT))
    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    engine = ServeEngine(
        model, params, n_slots=CHAT_SLOTS, tokens_per_launch=8,
        strategy=strategy,
    )
    assert engine.model.cfg.tp_mesh is strategy.mesh
    key = engine._state["cache"]["layers"]["block"]["attn"]["cached_key"]
    assert key.sharding.shard_shape(key.shape) == (24, 32, 2048, 2, 128)
    hlo = (
        jax.jit(engine._chain_fn, donate_argnums=(1,))
        .lower(engine.params, engine._state).compile().as_text()
    )
    assert "decode_attention" not in hlo and "tpu_custom_call" not in hlo
    # K and V stay split by head: the Megatron all-reduces and no gather
    assert "all-reduce" in hlo and "all-gather" not in hlo


def test_ssd_update_compiles(one_chip):
    """One Mamba-2 layer's decode step at Falcon-H1-34B's sizes (64 slots,
    32 heads of 128 by 256 states in 2 groups, a stack of 6 layers): the
    stack is donated into the result and nothing is made beside it."""
    from pytorch_distributed_training_tutorials_tpu.ops.ssd import ssd_update

    shapes = (
        _sds((6, 64, 32, 256, 128), jnp.float32), _sds((), jnp.int32),
        _sds((64, 32), jnp.float32), _sds((64, 32, 128), jnp.float32),
        _sds((64, 2, 256), jnp.float32), _sds((64, 2, 256), jnp.float32),
        _sds((64,), jnp.int32),
    )
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes,
    )
    compiled = jax.jit(
        lambda s, l, d, x, b, c, p: ssd_update(
            s, l, d, x, b, c, p, 4096, interpret=False),
        donate_argnums=0,
    ).lower(*args).compile()
    assert re.search(r"%ssd_update[\w.]* = ", compiled.as_text())
    analysis = compiled.memory_analysis()
    assert analysis.alias_size_in_bytes >= 6 * 64 * 32 * 256 * 128 * 4
    assert analysis.temp_size_in_bytes < 1 << 26, analysis.temp_size_in_bytes


@pytest.mark.parametrize("program", ["_chain_fn", "_prefill_fn"])
def test_parallel_block_programs_copy_no_cache(one_chip, monkeypatch, program):
    """``ServeEngine``'s chain and its 2,048 prefill for a model with a
    Mamba-2 mixer beside attention in every block, at Falcon-H1-34B's
    published widths (6 of 72 layers, 64 slots x 4,096 positions, int8,
    the 261,120-wide head): the K and V stacks (four KV heads with their
    rows together: innermost, four bfloat16 heads pad a sublane tile to
    four times the bytes) and the state stack ride the one layer scan as
    carries, ``decode_attention`` and ``ssd_update`` read them where they
    lie, every scanned product reads the stacked int8 weights at its layer
    index (``in_proj`` in whole lane tiles, the 32 ``dt`` columns a matrix
    of their own: at 9,248 columns the compiler relaid the 283 MB stack on
    its way into every launch), and the whole fits a 16 GB chip beside the
    1.34 GB int8 embedding the benchmark's reference reads (ISSUE 36).
    Shapes only."""
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.serve import ServeEngine
    from pytorch_distributed_training_tutorials_tpu.serve import (
        engine as engine_module,
    )
    from pytorch_distributed_training_tutorials_tpu.serve.slots import (
        init_slot_state,
        tree_nbytes,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        engine_module, "init_slot_state",
        lambda model, params, *a, **kw: jax.eval_shape(
            lambda p: init_slot_state(model, p, *a, **kw), params
        ),
    )
    model = TransformerLM(TransformerConfig(
        vocab_size=261120, d_model=5120, n_layers=6, n_heads=20,
        n_kv_heads=4, d_head=128, d_ff=21504, max_seq_len=4096,
        norm_eps=1e-5, rope_theta=1e11, mamba_n_heads=32, mamba_d_head=128,
        mamba_n_groups=2, mamba_d_state=256, mamba_chunk_size=128,
        embedding_multiplier=5.66, lm_head_multiplier=0.0078125,
        attention_out_multiplier=0.0375, key_multiplier=0.011,
        ssm_in_multiplier=0.25, ssm_out_multiplier=0.088,
        ssm_multipliers=(0.354, 0.25, 0.177, 0.5, 0.354),
        mlp_multipliers=(0.177, 0.0112), scan_layers=True, quantized=True,
        dtype=jnp.bfloat16, kv_cache_dtype=jnp.bfloat16,
        embedding_dtype=jnp.bfloat16,
    ))
    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    engine = ServeEngine(model, params, n_slots=64, tokens_per_launch=8)
    cache = engine._state["cache"]
    assert engine.stats("slot") == {
        "slot_kv_bytes": 6 * 2 * 4096 * 4 * 128 * 2, "slot_ring_bytes": 0,
        "slot_state_bytes": 6 * (32 * 256 * 128 + 3 * 5120) * 4,
    }
    args = (params, engine._state)
    if program == "_prefill_fn":
        i32 = _sds((), jnp.int32)
        args += (_sds((1, 2048), jnp.int32), i32, i32, i32, i32)
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        args,
    )
    compiled = (
        jax.jit(getattr(engine, program), donate_argnums=(1,))
        .lower(*placed).compile()
    )
    hlo = compiled.as_text()
    analysis = compiled.memory_analysis()
    # the slot tree is donated into the result; beside it the chain makes
    # 70 MB, the prefill a bucket's activations (0.56 GB); arguments and
    # temporaries leave room for the reference's 1.34 GB under 16 GB
    assert analysis.alias_size_in_bytes >= tree_nbytes(cache)
    limit = (1 << 27) if program == "_chain_fn" else 1 << 30
    assert analysis.temp_size_in_bytes < limit, analysis.temp_size_in_bytes
    assert (analysis.argument_size_in_bytes + analysis.temp_size_in_bytes
            + 261120 * 5120) < 15 << 30
    # no int8 weight of more than a megabyte is made (the 32 dt columns'
    # stack, 1 MB, is relaid: no whole lane tile)
    made = [
        (op, dims) for dims, layout, op in re.findall(
            r"= s8\[([\d,]+)\](\S*) ([\w\-]+)\(", hlo)
        if op not in ("parameter", "get-tuple-element", "bitcast", "copy-done")
        and "S(1)" not in layout
        and math.prod(int(n) for n in dims.split(",")) > 6 * 5120 * 32
    ]
    assert made == [], made
    # every result the size of a stack IS a stack, updated in place
    big = {
        (kind, op, dims) for kind, dims, op in re.findall(
            r"= (bf16|f32)\[([\d,]+)\]\S* ([\w\-]+)\(", hlo)
        if math.prod(int(n) for n in dims.split(",")) >= 6 * 64 * 4 * 4096 * 128
        and op not in ("parameter", "get-tuple-element", "bitcast", "while",
                       "tuple")
    }
    stacks = {"6,64,4,4096,128", "6,64,32,256,128"}
    assert all(
        dims in stacks and op not in ("copy", "dynamic-slice", "transpose")
        for _, op, dims in big
    ), big
    assert "261120,5120" not in "".join(
        line for line in hlo.splitlines()
        if " convert(" in line or " copy(" in line)
    if program == "_chain_fn":
        assert len(set(re.findall(r"%(ssd_update[\w.]*) = ", hlo))) >= 1
        assert re.search(r"%decode_attention[\w.]* = bf16\[64,20,128\]", hlo)
    stacked = re.findall(
        r"%int8_matmul[\w.]* = f32\[[\d,]+\]\S* custom-call\(.*?"
        r"operand_layout_constraints=\{(s32\[1\]\{0\}, .*?)\}, frontend", hlo)
    # q, k, v, o; in_proj, dt_proj, out_proj; gate, up, down
    assert len(stacked) == 10, len(stacked)


LONG = dict(  # benchmark/configs/mistral-7b-v0.1.json, serve mode
    vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    d_ff=14336, max_seq_len=4096, rope_theta=1e4, norm_eps=1e-5,
    dtype=jnp.float32, scan_layers=True, kv_cache_dtype=jnp.bfloat16,
)
LONG_SLOTS = 8


def test_long_prefill_attends_through_the_flash_kernel(one_chip, monkeypatch):
    """``ServeEngine``'s prefill at the long cell's widths (32 layers, 32
    query heads over 8 KV heads of 128, float32 activations, 8 slots x
    4,096 positions, int8 weights) at ``bucket`` 4,096: its causal attention
    is ``flash_attention_fwd`` with K and V at their 8 stored heads, rounded
    to bfloat16 where they are made (what the dense form's products did with
    float32 operands under XLA's default precision). No ``f32[32,4096,4096]``
    score matrix exists (three fusions over it were 0.57 s of a 3 s window
    before ISSUE 37), nothing of its size does, and the program's temporaries
    are half what they were (3.36 GB on the parent). Shapes only."""
    engine, params = _int8_engine_of_shapes(monkeypatch, LONG, LONG_SLOTS)
    i32 = _sds((), jnp.int32)
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (params, engine._state, _sds((1, 4096), jnp.int32), i32, i32, i32,
         i32),
    )
    compiled = (
        jax.jit(engine._prefill_fn, donate_argnums=(1,))
        .lower(*placed).compile()
    )
    hlo = compiled.as_text()
    call = re.search(
        r"%flash_attention_fwd[\w.]* = \(f32\[32,4096,128\]\S*, "
        r"f32\[32,8,4096\]\S*\) custom-call\(.*?"
        r"operand_layout_constraints=\{(.*?)\}, frontend_attributes", hlo)
    assert call, "no flash_attention_fwd kernel in the prefill"
    assert call.group(1) == (
        "bf16[32,4096,128]{2,1,0}, bf16[8,4096,128]{2,1,0}, "
        "bf16[8,4096,128]{2,1,0}"
    )
    assert "layers/block/attn/prefill_attn/flash_attention_fwd" in hlo
    # no score matrix, nor anything of its size: the float (and mask)
    # arrays larger than an MLP product of the 4,096 positions are the
    # embedding, the slots' cache stacks and the new cache's
    mlp = 4096 * LONG["d_ff"]
    large = {
        f"{dtype}[{dims}]"
        for dtype, dims in re.findall(r"= (f32|bf16|pred)\[([\d,]+)\]", hlo)
        if math.prod(int(n) for n in dims.split(",")) > mlp
    }
    assert large == {
        "f32[32000,4096]", "bf16[32,8,4096,8,128]", "bf16[32,1,4096,8,128]",
    }, large
    # K and V enter the kernel at 8 heads (above) and no product is left
    # under the scope beside it: nothing multiplies a 32-head copy of them
    beside = [
        line for line in hlo.splitlines()
        if "/prefill_attn/" in line and "flash_attention_fwd" not in line
    ]
    assert beside and not any(
        re.search(r" (convolution|dot)\(", line) for line in beside
    )
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 1024**3

"""The names the device trace tells the program's work apart by (ISSUE 27):
a unique ``name=`` on every ``pl.pallas_call`` of ``ops/``, the
``jax.named_scope``s around the cache, the sampler, the loss and the
optimizer, and the jitted programs' module names (the trace's
``XLA Modules`` line; ``benchmark/lib/program_trace.py`` reads all three).
Metadata only: nothing here may change what a program computes."""

import ast
import pathlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest

import pytorch_distributed_training_tutorials_tpu as pkg
from pytorch_distributed_training_tutorials_tpu import create_mesh
from pytorch_distributed_training_tutorials_tpu.data import ShardedLoader
from pytorch_distributed_training_tutorials_tpu.models import MLP
from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from pytorch_distributed_training_tutorials_tpu.serve import ServeEngine
from pytorch_distributed_training_tutorials_tpu.train import Trainer
from tests.helpers import make_cls_dataset

OPS = pathlib.Path(pkg.__file__).parent / "ops"
KERNELS = {
    "flash_attention_fwd": "flash_attention.py",
    "flash_attention_dq": "flash_attention.py",
    "flash_attention_dkv": "flash_attention.py",
    "fused_loss_fwd": "fused_loss.py",
    "fused_loss_dh": "fused_loss.py",
    "fused_loss_dw": "fused_loss.py",
    "fused_adamw": "fused_optim.py",
    "paged_attention": "paged_attention.py",
    "int8_matmul": "quant.py",
    "grouped_int8_matmul": "quant.py",
    "latent_decode_attention": "latent_attention.py",
    "decode_attention": "decode_attention.py",
    "selective_scan": "selective_scan.py",
    "ssd_update": "ssd.py",
}


def _pallas_calls():
    """[(file, line, name or None)] of every ``pl.pallas_call`` in ops/."""
    out = []
    for path in sorted(OPS.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                name = next(
                    (k.value.value for k in node.keywords
                     if k.arg == "name" and isinstance(k.value, ast.Constant)),
                    None)
                out.append((path.name, node.lineno, name))
    return out


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_pallas_call_is_named(kernel):
    calls = _pallas_calls()
    mine = [c for c in calls if c[2] == kernel]
    assert len(mine) == 1, f"{kernel}: {mine}"  # there, and no two share it
    assert mine[0][0] == KERNELS[kernel]


def test_every_pallas_call_has_a_name_of_its_own():
    calls = _pallas_calls()
    assert len(calls) == len(KERNELS)
    assert all(c[2] for c in calls), [c for c in calls if not c[2]]
    assert sorted(c[2] for c in calls) == sorted(KERNELS)


@pytest.fixture(scope="module")
def engine():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        max_seq_len=32, scan_layers=True,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return ServeEngine(model, params, n_slots=2, tokens_per_launch=2)


@pytest.fixture(scope="module")
def trainer():
    loader = ShardedLoader(make_cls_dataset(), 8, create_mesh({"data": 8}), seed=0)
    return Trainer(MLP(features=(32, 4)), loader, optax.adam(1e-3),
                   loss="cross_entropy", seed=0, quiet=True)


def _lowered(which, engine, trainer):
    if which == "chain":
        return engine._chain.lower(engine.params, engine._state)
    if which == "prefill":
        return engine._prefill.lower(
            engine.params, engine._state, jnp.zeros((1, 8), jnp.int32),
            5, 0, 0, 4)
    batch = next(iter(trainer.loader))
    return trainer.train_step.lower(trainer.state, batch)


@pytest.mark.parametrize("which,scope", [
    ("chain", "kv_cache"), ("chain", "sampling"), ("chain", "layer_scan"),
    ("prefill", "kv_cache"), ("prefill", "sampling"),
    ("train", "loss"), ("train", "optimizer"),
])
def test_scope_is_in_the_lowered_text(engine, trainer, which, scope):
    text = _lowered(which, engine, trainer).as_text(debug_info=True)
    # a scope under differentiation reads jvp(loss), transpose(jvp(loss))
    assert re.search(rf'[/"(]{scope}\)*/', text), scope
    if which == "train" and scope == "loss":
        # the backward pass of the loss carries the scope too
        assert "transpose(jvp(loss))" in text


@pytest.mark.parametrize("which,op", [
    # the chain is handed its cache: the layer scan carries it, and a layer's
    # write of its new rows and its read of cache[layer] are lines of the
    # program under the cell's module path (ISSUE 28)
    ("chain", "scatter"), ("chain", "dynamic_slice"),
    # the prefill creates its cache: the scan stacks each layer's own
    ("prefill", "dynamic_update_slice"),
])
def test_cache_ops_keep_the_module_path_under_the_scan(
        engine, trainer, which, op):
    """``layers/block/attn/kv_cache/<op>`` inside ``layer_scan``: what
    ``benchmark/lib/program_trace.py`` splits a step by, whichever way the
    scan treats the cache."""
    text = _lowered(which, engine, trainer).as_text(debug_info=True)
    assert f'"layers/block/attn/kv_cache/{op}"' in text
    assert re.search(r'[/"(]layer_scan\)*/', text)


def test_decode_kernel_is_under_its_scope_in_the_chain():
    """At ``head_dim`` 128 the chain's step reads K and V through the
    ``decode_attention`` kernel under ``layers/block/attn/decode_attn``
    (``decode_attention_share.*``), and ``cache[layer]`` is read by no
    ``dynamic_slice``; the new rows' ``scatter`` stays under ``kv_cache``."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=128, scan_layers=True,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=2)
    text = engine._chain.lower(engine.params, engine._state).as_text(
        debug_info=True)
    assert '"layers/block/attn/decode_attn/' in text
    assert '"layers/block/attn/kv_cache/scatter"' in text
    assert '"layers/block/attn/kv_cache/dynamic_slice"' not in text
    assert re.search(r'[/"(]layer_scan\)*/', text)


@pytest.mark.parametrize("bucket,kernel", [(1024, True), (64, False)],
                         ids=["kernel", "dense"])
def test_prefill_attention_is_under_its_scope_on_both_forms(bucket, kernel):
    """At ``head_dim`` 128 a prefill of a bucket from the threshold up
    attends through ``flash_attention_fwd`` under
    ``layers/block/attn/prefill_attn`` (``prefill_attention_share.*``), a
    shorter one through the dense form under the same scope: the trace says
    which ran and what it cost. The chain's program holds neither."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=1024, scan_layers=True,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=2)
    prefill = engine._prefill.lower(
        engine.params, engine._state, jnp.zeros((1, bucket), jnp.int32),
        5, 0, 0, 4).as_text(debug_info=True)
    assert "module @jit__prefill_fn " in prefill
    assert re.search(r'[/"(]layer_scan\)*/', prefill)
    under = re.findall(r'"layers/block/attn/prefill_attn/([^"]*)"', prefill)
    assert under
    assert ("flash_attention_fwd" in prefill) is kernel
    assert any("flash_attention_fwd" in u for u in under) is kernel
    # the dense form's score product, or the kernel (whose own products
    # the interpreter lowers under its name here)
    assert any(u.startswith("bqhd,bkhd->bhqk") for u in under) is not kernel
    assert '"layers/block/attn/kv_cache/dynamic_update_slice"' in prefill
    chain = engine._chain.lower(engine.params, engine._state).as_text(
        debug_info=True)
    assert "flash_attention_fwd" not in chain and "prefill_attn" not in chain


@pytest.mark.parametrize("which,module", [
    ("chain", "jit__chain_fn"), ("prefill", "jit__prefill_fn"),
    ("train", "jit_step_fn"),
])
def test_program_names_are_pinned(engine, trainer, which, module):
    """What the trace's ``XLA Modules`` line and ``hlo_module`` stat carry,
    and a part of the compile cache's key: renamed by nobody."""
    assert f"module @{module} " in _lowered(which, engine, trainer).as_text()


def _latent_engine(**kw):
    cfg = TransformerConfig(
        vocab_size=64, d_model=128, n_layers=3, n_heads=4, d_ff=128,
        max_seq_len=32, quantized=True,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, sandwich_norm=True,
        n_routed_experts=8, experts_held=4, experts_per_token=2,
        expert_d_ff=128, n_shared_experts=1, **kw,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return ServeEngine(model, params, n_slots=2, tokens_per_launch=2)


@pytest.fixture(scope="module")
def latent_engine():
    """Latent attention, a leading dense layer and layers of dropless
    routed experts with a shared one, int8, the layers unrolled: what the
    benchmark's cell runs (ISSUE 30)."""
    return _latent_engine(n_dense_layers=1, scan_layers=False)


@pytest.mark.parametrize("which", ["chain", "prefill"])
@pytest.mark.parametrize("scope", [
    "moe_router", "moe_dispatch", "moe_experts", "moe_shared", "latent_attn",
    "kv_cache", "mlp",
])
def test_latent_and_expert_scopes_are_in_the_lowered_text(
        latent_engine, which, scope):
    """What ``moe_share.serve``, ``moe_dispatch_share.serve`` and
    ``latent_attention_share.serve`` read (``benchmark/layer_metrics``)."""
    text = _lowered(which, latent_engine, None).as_text(debug_info=True)
    assert re.search(rf'[/"(]{scope}\)*/', text), scope


@pytest.mark.parametrize("scan_layers", [False, True], ids=["unrolled", "scan"])
def test_latent_cache_rows_are_written_under_kv_cache(scan_layers):
    """A latent cache's new rows go in under ``kv_cache`` and the two new
    kernels keep their names, unrolled and, for layers of experts alone,
    under the layer scan (where ``layer_scan_share.*`` tells a layer's own
    work from the scan's slicing by the scope ``layers``)."""
    engine = _latent_engine(n_dense_layers=0, scan_layers=scan_layers)
    text = _lowered("chain", engine, None).as_text(debug_info=True)
    layer = "layers/block" if scan_layers else "block_2"
    assert f'{layer}/attn/kv_cache/scatter"' in text
    assert bool(re.search(r'[/"(]layer_scan\)*/', text)) is scan_layers
    assert "grouped_int8_matmul" in text and "latent_decode_attention" in text


@pytest.mark.parametrize("scan_layers", [False, True], ids=["unrolled", "scan"])
@pytest.mark.parametrize("which", ["chain", "prefill"])
def test_int8_matmul_keeps_its_name_in_both_forms(which, scan_layers):
    """``name="int8_matmul"`` under ``<layer>/mlp/up_proj/int8_matmul/
    pallas_call`` whether the call reads a (k, n) weight (unrolled layers,
    the head) or its layer in the scanned stack at a scalar-prefetched
    index (ISSUE 33): what ``int8_matmul_stacked_roofline.*`` finds the
    calls by, and the by-scope table counts under ``mlp``."""
    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=2, d_ff=128,
        max_seq_len=32, quantized=True, scan_layers=scan_layers,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=2)
    text = _lowered(which, engine, None).as_text(debug_info=True)
    layer = "layers/block" if scan_layers else "block_1"
    assert f'{layer}/mlp/up_proj/int8_matmul/pallas_call"' in text
    assert 'lm_head/int8_matmul/pallas_call"' in text


@pytest.fixture(scope="module")
def state_engine():
    """Mamba, window, full, Gated Memory Unit and cross-attention layers by
    their place, int8, two layer scans: what the benchmark's cell runs
    (ISSUE 34)."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=128, n_layers=8, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=32, mb_per_layer=2, sliding_window=8,
        tie_embeddings=True, scan_layers=True, quantized=True,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return ServeEngine(model, params, n_slots=2, tokens_per_launch=2)


@pytest.mark.parametrize("which", ["chain", "prefill"])
@pytest.mark.parametrize("scope", [
    "ssm_conv", "ssm_scan", "gmu", "window_attn", "shared_kv_attn",
    "diff_combine", "kv_cache", "layer_scan", "layers", "mlp", "lm_head",
])
def test_recurrent_state_scopes_are_in_the_lowered_text(
        state_engine, which, scope):
    """What ``ssm_share.serve``, ``shared_kv_attention_share.serve``,
    ``window_attention_share.serve`` and ``gmu_share.serve`` read, and the
    cell ``layers`` inside ``layer_scan`` that ``layer_scan_share.serve``
    leaves out (``benchmark/layer_metrics``)."""
    text = _lowered(which, state_engine, None).as_text(debug_info=True)
    assert re.search(rf'[/"(]{scope}\)*/', text), scope


@pytest.mark.parametrize("which,path", [
    # a step's new row into a ring and into the shared cache, its state
    ("chain", "layers_a/layers/window_block/attn/kv_cache/scatter"),
    ("chain", "block_5/attn/kv_cache/scatter"),
    ("chain", "layers_a/layers/kv_cache/scatter"),
    # a prompt's ring, its K and V, its state
    ("prefill", "layers_a/layers/window_block/attn/kv_cache/scatter"),
    ("prefill", "block_5/attn/kv_cache/dynamic_update_slice"),
    ("prefill", "layers_a/layers/kv_cache/scatter"),
    # the products of both scans read the stacked weights by name
    ("chain", "layers_a/layers/mamba_block/mixer/in_proj/int8_matmul/pallas_call"),
    ("chain", "layers_b/layers/gmu_block/mixer/gmu/in_proj/int8_matmul/pallas_call"),
    ("chain", "layers_b/layers/cross_block/attn/shared_kv_attn/"),
])
def test_recurrent_state_writes_keep_their_paths(state_engine, which, path):
    """The ring's, the shared cache's and the state's writes lie under
    ``kv_cache`` at their layer's path (a scan's body names its own from
    the scanned module down: ``layers_a/layers/...``)."""
    text = _lowered(which, state_engine, None).as_text(debug_info=True)
    assert path in text, path


@pytest.fixture(scope="module")
def parallel_engine():
    """A Mamba-2 mixer beside attention in every block, heads of 128 and a
    state of whole tiles, int8, one layer scan: what the benchmark's
    Falcon-H1 cell runs (ISSUE 36)."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=64, n_layers=2, n_heads=2, n_kv_heads=1,
        d_head=128, d_ff=64, max_seq_len=128, scan_layers=True,
        mamba_n_heads=2, mamba_d_head=128, mamba_n_groups=1,
        mamba_d_state=16, mamba_chunk_size=8, quantized=True,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return ServeEngine(model, params, n_slots=2, tokens_per_launch=2)


@pytest.mark.parametrize("which", ["chain", "prefill"])
@pytest.mark.parametrize("scope", [
    "ssm_conv", "ssm_scan", "ssm_gate_norm", "attn", "mamba", "kv_cache",
    "layer_scan", "layers", "mlp", "lm_head",
])
def test_parallel_block_scopes_are_in_the_lowered_text(
        parallel_engine, which, scope):
    """What ``ssm_share.serve`` reads, the gate and the grouped norm (the
    by-scope table's), and the attention branch under the names it has in
    every other model."""
    text = _lowered(which, parallel_engine, None).as_text(debug_info=True)
    assert re.search(rf'[/"(]{scope}\)*/', text), scope


@pytest.mark.parametrize("which,path", [
    # a step: the state's kernel, the attention branch's, its new rows
    ("chain", "layers/block/mamba/ssm_scan/ssd_update/pallas_call"),
    ("chain", "layers/block/attn/decode_attn/"),
    ("chain", "layers/block/attn/kv_cache/scatter"),
    ("chain", "layers/block/mamba/ssm_conv/dynamic_update_slice"),
    # a prompt: its K and V, the chunked form's products, its state
    ("prefill", "layers/block/attn/kv_cache/dynamic_update_slice"),
    ("prefill", "layers/block/mamba/ssm_scan/"),
    ("prefill", "layers/block/mamba/ssm_gate_norm/"),
    # both branches' products read the stacked weights by name
    ("chain", "layers/block/mamba/in_proj/int8_matmul/pallas_call"),
    ("chain", "layers/block/mamba/dt_proj/int8_matmul/pallas_call"),
    ("chain", "layers/block/attn/q_proj/int8_matmul/pallas_call"),
])
def test_parallel_block_keeps_its_paths(parallel_engine, which, path):
    """``ssd_update`` under ``ssm_scan`` (``ssd_update_share.serve`` reads
    the kernel's name, ``ssm_share.serve`` the scope), the attention branch
    under ``attn/decode_attn`` and ``attn/kv_cache`` as in every model
    (that no stack is copied is ``tests/test_chip_compile.py``'s)."""
    text = _lowered(which, parallel_engine, None).as_text(debug_info=True)
    assert path in text, path


def test_decode_kernel_is_under_its_cache_kind_in_the_chain():
    """A model whose window layers keep a ring (``models/sambay.py``) at
    widths the kernel takes (a KV pair of 2 x 64): the ring's calls under
    ``window_attn/decode_attn``, the shared cache's under
    ``shared_kv_attn/decode_attn``. ``decode_attention_roofline.serve``
    charges a call under ``window_attn`` the chain's ``ring_rows`` and every
    other its ``kv_rows`` (``benchmark/lib/decode_roofline.py``)."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=256, n_layers=8, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, mb_per_layer=2, sliding_window=128,
        tie_embeddings=True, scan_layers=True,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    engine = ServeEngine(model, params, n_slots=2, tokens_per_launch=2)
    assert engine._ring == 128
    text = _lowered("chain", engine, None).as_text(debug_info=True)
    assert "module @jit__chain_fn " in text
    assert "layers_a/layers/window_block/attn/window_attn/decode_attn/" in text
    assert "layers_b/layers/cross_block/attn/shared_kv_attn/decode_attn/" in text
    assert re.search(r'block_\d+/attn/shared_kv_attn/decode_attn/', text)

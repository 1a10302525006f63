"""Auto placement / checkpointing: the device_map="auto" twin."""

import os
import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from pytorch_distributed_training_tutorials_tpu.models import MLP
from pytorch_distributed_training_tutorials_tpu.parallel.auto import (
    audit_placement,
    load_sharded,
    restore_checkpoint,
    save_checkpoint,
)
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh


def _params():
    m = MLP(features=(64, 8))
    return m.init(jax.random.PRNGKey(0), jnp.zeros((1, 16)))["params"]


def test_save_restore_roundtrip(tmp_path):
    params = _params()
    p = os.path.join(tmp_path, "ckpt")
    save_checkpoint(p, params)
    save_checkpoint(p, params)  # overwrite of an existing path must succeed
    back = restore_checkpoint(p)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params,
        back,
    )


def test_load_sharded_places_on_mesh(tmp_path):
    """Restore straight to the mesh: dim-0-sharded kernels, replicated biases
    — placement by declaration, the accelerate-device-map twin."""
    params = _params()
    p = os.path.join(tmp_path, "ckpt")
    save_checkpoint(p, params)
    mesh = create_mesh({"data": 8})

    def rule(path, leaf):
        if leaf.shape and leaf.shape[0] % 8 == 0:
            return NamedSharding(mesh, PartitionSpec("data"))
        return NamedSharding(mesh, PartitionSpec())

    placed = load_sharded(p, rule)
    k0 = placed["Dense_0"]["kernel"]  # (16, 64): dim0 16 % 8 == 0 -> sharded
    assert len(k0.devices()) == 8
    assert k0.sharding.spec == PartitionSpec("data")
    b0 = placed["Dense_0"]["bias"]  # (64,) % 8 == 0 -> sharded too
    assert b0.sharding.spec == PartitionSpec("data")
    # values identical to the host originals
    np.testing.assert_allclose(
        np.asarray(k0), np.asarray(params["Dense_0"]["kernel"])
    )


def test_restore_with_like_tree(tmp_path):
    params = _params()
    p = os.path.join(tmp_path, "ckpt")
    save_checkpoint(p, params)
    like = jax.tree_util.tree_map(np.zeros_like, params)
    back = restore_checkpoint(p, like)
    np.testing.assert_allclose(
        np.asarray(back["Dense_1"]["kernel"]),
        np.asarray(params["Dense_1"]["kernel"]),
    )


def test_audit_placement_lines():
    params = _params()
    mesh = create_mesh()
    placed = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    lines = audit_placement(placed)
    assert len(lines) == 4  # 2 layers x (kernel, bias)
    assert all("devices" in line for line in lines)
    host_lines = audit_placement(params)
    # CPU-backend arrays still live on a device; just check it doesn't crash
    assert len(host_lines) == 4


_RSS_CHILD = """
import os, sys, json
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np

path, mode = sys.argv[1], sys.argv[2]
from pytorch_distributed_training_tutorials_tpu.parallel.auto import (
    load_quantized, restore_checkpoint,
)
from pytorch_distributed_training_tutorials_tpu.ops.quant import (
    Int8Param, quantize_int8,
)

def status_kb(field):
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(field + ":"):
                return int(line.split()[1])
    raise RuntimeError(field)

# imports peak >1 GB; reset the kernel's high-water mark so VmHWM measures
# only the load itself
with open("/proc/self/clear_refs", "w") as f:
    f.write("5")
base = status_kb("VmRSS")
if mode == "stream":
    tree = load_quantized(path)
else:  # the old full-materialization path, as the comparison baseline
    full = restore_checkpoint(path)
    tree = jax.tree_util.tree_map(
        lambda a: quantize_int8(a) if getattr(a, "ndim", 0) >= 2 else a, full
    )
    del full
n_q = sum(
    isinstance(x, Int8Param)
    for x in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, Int8Param)
    )
)
peak = status_kb("VmHWM")
print(json.dumps({"base_kb": base, "peak_kb": peak, "quantized": n_q}))
"""


@pytest.mark.slow
def test_load_quantized_streams_bounded_host_peak(tmp_path):
    """Round-1 review item: quantize-on-load must NOT materialize the f32
    checkpoint on host. A 768 MB checkpoint (24 x 32 MB kernels, the
    33-shard-Llama pattern at test scale) is loaded in a fresh subprocess
    twice; the streaming path's peak RSS must undercut the
    full-materialization path by a checkpoint-sized margin."""
    import json
    import subprocess
    import sys

    n_leaf, shape = 24, (2048, 4096)
    leaf_bytes = shape[0] * shape[1] * 4  # 32 MB
    rng = np.random.Generator(np.random.PCG64(0))
    tree = {
        f"layer_{i}": {
            "kernel": rng.standard_normal(shape).astype(np.float32),
            "norm_scale": np.ones((shape[0],), np.float32),
        }
        for i in range(n_leaf)
    }
    path = os.path.join(tmp_path, "big_ckpt")
    save_checkpoint(path, tree)
    del tree

    def run(mode):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", _RSS_CHILD, path, mode],
            env=env, capture_output=True, text=True, timeout=600,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    stream = run("stream")
    full = run("full")
    assert stream["quantized"] == n_leaf
    assert full["quantized"] == n_leaf
    stream_delta = (stream["peak_kb"] - stream["base_kb"]) * 1024
    full_delta = (full["peak_kb"] - full["base_kb"]) * 1024
    ckpt_bytes = n_leaf * leaf_bytes  # 768 MB of f32 kernels
    # full path holds all f32 leaves at once; streaming holds ~1 + int8 tree
    assert full_delta > 0.9 * ckpt_bytes, (stream_delta, full_delta)
    assert stream_delta < full_delta - 0.4 * ckpt_bytes, (
        stream_delta, full_delta,
    )
    # absolute sanity bound: int8 result (ckpt/4) + per-leaf f32 transients
    # + tensorstore cache slack stays well under the f32 checkpoint (the
    # O(largest-leaf) scaling claim is carried by the relative assert above)
    assert stream_delta < 0.75 * ckpt_bytes, stream_delta


def test_load_quantized_sharded_onto_mesh(tmp_path):
    """8-bit load composed with mesh auto placement: each leaf restores
    straight to the 8-device mesh, quantized weights end up sharded (the
    full device_map='auto' + load_in_8bit combination, reference 03 cell 2),
    and the cell-4-style audit reports int8 + f32 placements."""
    from pytorch_distributed_training_tutorials_tpu.ops.quant import Int8Param
    from pytorch_distributed_training_tutorials_tpu.parallel.auto import (
        audit_placement,
        load_quantized,
    )

    rng = np.random.Generator(np.random.PCG64(1))
    tree = {
        f"layer_{i}": {
            "kernel": rng.standard_normal((64, 128)).astype(np.float32),
            "bias": np.zeros((128,), np.float32),
        }
        for i in range(3)
    }
    path = os.path.join(tmp_path, "mesh_ckpt")
    save_checkpoint(path, tree)
    mesh = create_mesh()

    def sharding_fn(kp, meta):
        spec = (
            PartitionSpec(None, "data")
            if len(meta.shape) >= 2
            else PartitionSpec()
        )
        return NamedSharding(mesh, spec)

    loaded = load_quantized(path, sharding_fn=sharding_fn)
    k = loaded["layer_0"]["kernel"]
    assert isinstance(k, Int8Param)
    assert k.q.dtype == jnp.int8
    # quantized on device, still mesh-sharded: 128 cols / 8 devices
    assert k.q.sharding.spec == PartitionSpec(None, "data")
    assert k.q.addressable_shards[0].data.shape == (64, 16)
    np.testing.assert_allclose(
        np.asarray(k.dequantize()),
        tree["layer_0"]["kernel"],
        atol=float(np.asarray(k.scale).max()) / 2 + 1e-7,
    )
    lines = audit_placement(loaded)
    assert any("int8" in ln for ln in lines)


def test_device_materialize_identity_and_sharding():
    """device_materialize must be an exact identity that preserves tree
    structure, dtypes, non-array leaves, and mesh placement — its only job
    is to turn host-put buffers into XLA-computed (device-resident) ones
    (one bandwidth pass, a no-op semantically)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
    from pytorch_distributed_training_tutorials_tpu.utils.tree import (
        device_materialize,
    )

    mesh = create_mesh()
    sharded = jax.device_put(
        np.arange(64, dtype=np.float32).reshape(8, 8),
        NamedSharding(mesh, PartitionSpec("data", None)),
    )
    tree = {
        "a": jax.device_put(np.arange(6, dtype=np.int8)),
        "b": {"c": sharded, "n": 7},   # non-array leaf passes through
        "d": jnp.float32(2.5),
    }
    out = device_materialize(tree)
    assert jax.tree_util.tree_structure(out) == (
        jax.tree_util.tree_structure(tree)
    )
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    assert out["a"].dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(out["b"]["c"]), np.asarray(sharded))
    assert out["b"]["n"] == 7 and isinstance(out["b"]["n"], int)
    assert out["d"] == jnp.float32(2.5)
    # placement preserved through the jitted identity (spec normalizes
    # trailing None away; compare the effective per-device shards)
    assert out["b"]["c"].sharding.spec in (
        PartitionSpec("data", None), PartitionSpec("data"),
    )
    assert (
        out["b"]["c"].addressable_shards[0].data.shape
        == sharded.addressable_shards[0].data.shape
    )

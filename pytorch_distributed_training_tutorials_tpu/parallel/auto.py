"""Auto placement + checkpointing: the ``device_map="auto"`` twin (orbax).

Reference capability (SURVEY.md C13): ``from_pretrained(..., device_map="auto")``
streams 33 checkpoint shards and lets accelerate's memory packer decide which
device each weight lands on (``03.model_parallel.ipynb:52-57``); the tutorial
then audits every param's device/dtype (cell 4, ``:409``).

TPU-native design: placement comes from *sharding annotations*, not a greedy
packer — a checkpoint is restored directly into device memory with a
per-parameter ``jax.sharding.Sharding``, so a model larger than one chip's HBM
loads sharded across the mesh without ever materializing on one device. The
same machinery closes the reference's checkpoint/resume gap (SURVEY.md
section 5.4: the reference never calls ``torch.save``; restarts retrain from
scratch).
"""

from __future__ import annotations

import os
from collections.abc import Callable

import jax
import numpy as np
import orbax.checkpoint as ocp

from pytorch_distributed_training_tutorials_tpu.utils.tree import keystr as _keystr


def save_checkpoint(path: str | os.PathLike, tree) -> None:
    """Write a pytree (params / full train-state) as a sharded checkpoint.

    Overwrites an existing checkpoint at ``path`` (``force=True`` — orbax
    removes the old directory on the primary host with its own cross-host
    synchronization). Each host writes only its addressable shards, the
    multi-host twin of the reference's 33-shard checkpoint layout.
    """
    path = os.path.abspath(path)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(path, tree, force=True)


def restore_checkpoint(path: str | os.PathLike, like=None):
    """Restore a checkpoint; with ``like=None`` restores as host numpy."""
    with ocp.StandardCheckpointer() as ckptr:
        if like is None:
            return ckptr.restore(os.path.abspath(path))
        return ckptr.restore(os.path.abspath(path), like)


def load_sharded(
    path: str | os.PathLike,
    sharding_fn: Callable[[tuple, jax.ShapeDtypeStruct], jax.sharding.Sharding],
):
    """Restore a checkpoint straight onto devices, placed per-parameter.

    ``sharding_fn(key_path, abstract_leaf) -> Sharding`` is the declarative
    twin of accelerate's ``infer_auto_device_map``: instead of a greedy
    memory-fit pass, the caller states where every weight lives (replicated,
    batch-axis sharded, stage-placed, ...) and orbax restores each shard
    directly into that placement — no full-model host materialization.

    The restored tree is passed through
    :func:`..utils.tree.device_materialize` (a jitted exact identity) so
    every leaf is guaranteed device-resident: trees that pick up host
    numpy leaves anywhere get re-uploaded by jit on every consuming call
    (what that costs on the chip: not measured); a training step's donated
    update would fix params after one step, but eval/serving never
    rewrites them.
    """
    path = os.path.abspath(path)
    with ocp.StandardCheckpointer() as ckptr:
        meta = ckptr.metadata(path)
        abstract = jax.tree_util.tree_map_with_path(
            lambda kp, m: jax.ShapeDtypeStruct(
                m.shape,
                m.dtype,
                sharding=sharding_fn(tuple(kp), m),
            ),
            meta.item_metadata if hasattr(meta, "item_metadata") else meta,
        )
        restored = ckptr.restore(path, abstract)

    from pytorch_distributed_training_tutorials_tpu.utils.tree import device_materialize

    return device_materialize(restored)


def checkpoint_leaf_metadata(path: str | os.PathLike):
    """Flat ``(key_path, array_metadata)`` list + treedef for a checkpoint."""
    path = os.path.abspath(path)
    with ocp.StandardCheckpointer() as ckptr:
        meta = ckptr.metadata(path)
        tree = meta.item_metadata if hasattr(meta, "item_metadata") else meta
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return flat, treedef


def restore_leaf(
    path: str | os.PathLike,
    key_path: tuple,
    meta,
    sharding: jax.sharding.Sharding | None = None,
    checkpointer: ocp.Checkpointer | None = None,
):
    """Restore exactly one leaf from a checkpoint (no other IO happens —
    the other leaves are never read, so host peak is this leaf's size).

    With ``sharding``, the leaf deserializes *straight into device memory*
    with that placement; otherwise it lands as host numpy. Pass an open
    ``checkpointer`` when restoring many leaves in a loop (one handler,
    not one per leaf).
    """
    keys = tuple(
        str(getattr(k, "key", getattr(k, "idx", k))) for k in key_path
    )
    sds = jax.ShapeDtypeStruct(meta.shape, meta.dtype)
    item: object = sds
    restore_arg: object = (
        ocp.ArrayRestoreArgs(sharding=sharding)
        if sharding is not None
        else ocp.RestoreArgs(restore_type=np.ndarray)
    )
    for k in reversed(keys):
        item = {k: item}
        restore_arg = {k: restore_arg}

    def _restore(ckptr):
        return ckptr.restore(
            os.path.abspath(path),
            args=ocp.args.PyTreeRestore(
                item=item, transforms={}, restore_args=restore_arg
            ),
        )

    if checkpointer is not None:
        out = _restore(checkpointer)
    else:
        with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ckptr:
            out = _restore(ckptr)
    for k in keys:
        out = out[k]
    return out


def load_quantized(
    path: str | os.PathLike,
    should_quantize: Callable[[str, np.ndarray], bool] | None = None,
    channel_axis: int = -1,
    sharding_fn: Callable | None = None,
):
    """Restore a checkpoint with selected weights quantized to int8 on load,
    **streaming one leaf at a time**.

    The ``load_in_8bit=True`` twin (reference ``03.model_parallel.ipynb``
    cell 2, SURVEY.md C13): matmul weights come back as
    :class:`..ops.quant.Int8Param` (int8 values + per-channel float32
    scales, 1/4 the HBM) while norms/biases/embeddings stay float — the same
    mixed-precision layout the tutorial's param audit shows (cell 4).

    Each leaf is restored individually (:func:`restore_leaf`), quantized,
    and only then is the next leaf read — the float checkpoint is **never
    materialized in full**: peak host usage is the largest single leaf plus
    the (4x smaller) accumulated int8 tree, the same bound the reference
    gets from streaming its 33 shards through bitsandbytes one at a time.
    Verified by the RSS test in ``tests/test_auto.py``.

    ``should_quantize(path_str, leaf) -> bool`` selects the weights; the
    default quantizes every rank->=2 leaf whose path ends in ``kernel``.
    ``sharding_fn(key_path, meta) -> Sharding`` additionally places each
    restored leaf straight onto devices (quantization then runs on-device),
    composing 8-bit load with mesh-sharded auto placement — the full
    ``device_map="auto" + load_in_8bit`` combination.
    Serve the result with :class:`..ops.quant.Int8Dense`-style modules or
    by calling ``.dequantize()`` at use sites.
    """
    from pytorch_distributed_training_tutorials_tpu.ops.quant import quantize_int8

    if should_quantize is None:
        def should_quantize(p, leaf):  # noqa: F811
            return p.endswith("kernel") and getattr(leaf, "ndim", 0) >= 2

    path = os.path.abspath(path)
    out_flat = []
    flat_meta, treedef = checkpoint_leaf_metadata(path)
    with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ckptr:
        for kp, m in flat_meta:
            sharding = sharding_fn(tuple(kp), m) if sharding_fn else None
            leaf = restore_leaf(
                path, kp, m, sharding=sharding, checkpointer=ckptr
            )
            if should_quantize(_keystr(kp), leaf):
                q = quantize_int8(leaf, channel_axis=channel_axis)
                del leaf  # free the f32 before the next leaf is read
                leaf = q
            out_flat.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out_flat)


def audit_placement(tree) -> list[str]:
    """Per-leaf device/dtype audit lines.

    Twin of the reference's param audit loop (``03.model_parallel.ipynb``
    cell 4): ``for name, param: print(name, param.device, param.dtype)``.
    """
    lines = []

    def visit(kp, leaf):
        name = _keystr(kp)
        if isinstance(leaf, jax.Array):
            devs = sorted(d.id for d in leaf.devices())
            lines.append(f"{name}: {leaf.shape} {leaf.dtype} on devices {devs}")
        else:
            arr = np.asarray(leaf)
            lines.append(f"{name}: {arr.shape} {arr.dtype} on host")
        return leaf

    jax.tree_util.tree_map_with_path(visit, tree)
    return lines

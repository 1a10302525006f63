"""Published-checkpoint ingestion: HF-layout (safetensors) Llama weights.

The reference's flagship lesson loads a *published* pretrained Llama-7B
from the HF hub with quantize-on-load, streaming 33 shards
(``/root/reference/03.model_parallel.ipynb:52-57``). The orbax path
(:mod:`.auto`) covers checkpoints this framework wrote itself; this module
closes the external-format gap: a directory in the Hugging Face layout —
``config.json`` + ``model.safetensors`` (or ``model.safetensors.index.json``
plus shards) — loads into a :class:`..models.transformer.TransformerLM`
parameter tree, **streaming one tensor at a time** (host peak = the largest
single tensor plus the accumulated output tree, the same bound
:func:`.auto.load_quantized` gives orbax checkpoints), optionally
quantizing each matmul weight to int8 as it is read (the
``load_in_8bit=True`` twin) — entirely offline, no network.

The safetensors container is parsed directly (8-byte little-endian header
length, JSON header mapping tensor name -> dtype/shape/offsets, then raw
little-endian data) so per-tensor reads are plain ``seek`` + ``read`` —
no safetensors package dependency, nothing but numpy.

Weight-layout conventions handled (torch ``nn.Linear`` stores ``(out, in)``;
flax ``nn.Dense`` kernels are ``(in, out)``):

- ``model.embed_tokens.weight`` (V, d)        -> ``tok_emb/embedding`` (V, d)
- ``...self_attn.{q,k,v}_proj.weight`` (H*D, d) -> ``block_i/attn/{q,k,v}_proj/kernel``
  (d, H, D): transpose then split heads
- ``...self_attn.o_proj.weight`` (d, H*D)     -> ``block_i/attn/o_proj/kernel``
  (H, D, d): transpose then split heads
- ``...mlp.{gate,up}_proj.weight`` (ff, d)    -> ``(d, ff)`` transpose
- ``...mlp.down_proj.weight`` (d, ff)         -> ``(ff, d)`` transpose
- ``input_layernorm`` / ``post_attention_layernorm`` / ``model.norm``
  -> ``attn_norm`` / ``mlp_norm`` / ``final_norm`` scales
- ``lm_head.weight`` (V, d) -> ``lm_head/kernel`` (d, V); absent when
  ``tie_word_embeddings`` — then the embedding matrix is reused.

The rotary convention matches by construction: HF checkpoints are permuted
for the ``rotate_half`` formulation, which is exactly
:func:`..models.transformer.apply_rope`'s ``[:half] / [half:]`` split.
Logit parity against ``transformers.LlamaForCausalLM`` is pinned by
``tests/test_hf_llama.py`` (torch is the oracle, as in test_sampler.py).
"""

from __future__ import annotations

import json
import os
import struct

import numpy as np

# safetensors dtype tag -> numpy dtype. BF16 needs ml_dtypes (a jax
# dependency, always present here); torch's save path emits "F32"/"F16"/
# "BF16" for float checkpoints.
_DTYPES = {
    "F64": np.float64,
    "F32": np.float32,
    "F16": np.float16,
    "I64": np.int64,
    "I32": np.int32,
    "I16": np.int16,
    "I8": np.int8,
    "U8": np.uint8,
    "BOOL": np.bool_,
}


def _np_dtype(tag: str):
    if tag == "BF16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    try:
        return np.dtype(_DTYPES[tag])
    except KeyError:
        raise ValueError(f"unsupported safetensors dtype {tag!r}") from None


class SafetensorsFile:
    """Lazy per-tensor reader for one ``.safetensors`` file.

    ``get(name)`` seeks to that tensor's byte range and reads it alone —
    the file is never mapped or read whole, so host memory is bounded by
    the largest single tensor regardless of checkpoint size.
    """

    def __init__(self, path: str | os.PathLike):
        self.path = os.fspath(path)
        with open(self.path, "rb") as f:
            (header_len,) = struct.unpack("<Q", f.read(8))
            header = json.loads(f.read(header_len))
        self._data_start = 8 + header_len
        header.pop("__metadata__", None)
        self.tensors = {
            name: (
                str(info["dtype"]),
                tuple(info["shape"]),
                tuple(info["data_offsets"]),
            )
            for name, info in header.items()
        }

    def keys(self):
        return self.tensors.keys()

    def get(self, name: str) -> np.ndarray:
        dtype_tag, shape, (start, end) = self.tensors[name]
        dtype = _np_dtype(dtype_tag)
        with open(self.path, "rb") as f:
            f.seek(self._data_start + start)
            buf = f.read(end - start)
        arr = np.frombuffer(buf, dtype=dtype).reshape(shape)
        return arr


class HFCheckpoint:
    """A HF-layout checkpoint directory: tensor name -> file resolution.

    Handles the single-file layout (``model.safetensors``), the sharded
    layout (``model.safetensors.index.json`` with a ``weight_map``), and a
    bare glob of ``*.safetensors`` shards (each shard's own header lists
    its tensors — the index file is an optimization, not a requirement).
    """

    def __init__(self, path: str | os.PathLike):
        self.dir = os.fspath(path)
        index = os.path.join(self.dir, "model.safetensors.index.json")
        self._files: dict[str, SafetensorsFile] = {}
        self._where: dict[str, str] = {}
        if os.path.exists(index):
            with open(index) as f:
                weight_map = json.load(f)["weight_map"]
            for name, fname in weight_map.items():
                self._where[name] = os.path.join(self.dir, fname)
        else:
            shards = sorted(
                fn
                for fn in os.listdir(self.dir)
                if fn.endswith(".safetensors")
            )
            if not shards:
                raise FileNotFoundError(
                    f"no .safetensors files under {self.dir}"
                )
            for fn in shards:
                full = os.path.join(self.dir, fn)
                for name in SafetensorsFile(full).keys():
                    self._where[name] = full

    def __contains__(self, name: str) -> bool:
        return name in self._where

    def keys(self):
        return self._where.keys()

    def get(self, name: str) -> np.ndarray:
        path = self._where[name]
        f = self._files.get(path)
        if f is None:
            f = self._files[path] = SafetensorsFile(path)
        return f.get(name)


def config_from_hf(path: str | os.PathLike, **overrides):
    """Build a :class:`TransformerConfig` from a checkpoint's ``config.json``.

    Maps the HF Llama field names (hidden_size, num_hidden_layers,
    num_attention_heads, num_key_value_heads, intermediate_size,
    max_position_embeddings, rope_theta, rms_norm_eps) onto the framework
    config. ``overrides`` win — e.g. ``max_seq_len=2080`` to serve with a
    smaller cache than the model's trained maximum.
    """
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        TransformerConfig,
    )

    with open(os.path.join(os.fspath(path), "config.json")) as f:
        hf = json.load(f)
    act = hf.get("hidden_act", "silu")
    if act not in ("silu", "swish"):
        raise ValueError(
            f"unsupported hidden_act {act!r}: TransformerLM's FFN is "
            "SwiGLU (silu) — loading this checkpoint would silently "
            "change the activation"
        )
    if hf.get("rope_scaling") is not None:
        raise ValueError(
            "rope_scaling is not supported: apply_rope implements plain "
            "rotary embedding; a scaled-rope checkpoint would produce "
            "wrong positions beyond the original context"
        )
    kw = dict(
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads"),
        d_ff=hf["intermediate_size"],
        max_seq_len=hf.get("max_position_embeddings", 2048),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def _llama_layer_entries(i: int, cfg):
    """(our relative path, hf tensor name, transform) for layer ``i``.

    Transforms take the raw (already dtype-cast) numpy array to the flax
    kernel layout. ``d`` = d_model, ``h``/``kv`` = query/KV head counts.
    """
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    pre = f"model.layers.{i}."

    def qkv(heads):
        return lambda w: np.ascontiguousarray(w.T).reshape(d, heads, hd)

    def o(w):
        return np.ascontiguousarray(w.T).reshape(h, hd, d)

    def t(w):
        return np.ascontiguousarray(w.T)

    return [
        (("attn", "q_proj", "kernel"), pre + "self_attn.q_proj.weight", qkv(h)),
        (("attn", "k_proj", "kernel"), pre + "self_attn.k_proj.weight", qkv(kv)),
        (("attn", "v_proj", "kernel"), pre + "self_attn.v_proj.weight", qkv(kv)),
        (("attn", "o_proj", "kernel"), pre + "self_attn.o_proj.weight", o),
        (("attn_norm", "scale"), pre + "input_layernorm.weight", None),
        (("mlp", "gate_proj", "kernel"), pre + "mlp.gate_proj.weight", t),
        (("mlp", "up_proj", "kernel"), pre + "mlp.up_proj.weight", t),
        (("mlp", "down_proj", "kernel"), pre + "mlp.down_proj.weight", t),
        (("mlp_norm", "scale"), pre + "post_attention_layernorm.weight", None),
    ]


def _set(tree: dict, path: tuple, leaf) -> None:
    node = tree
    for k in path[:-1]:
        node = node.setdefault(k, {})
    node[path[-1]] = leaf


def load_hf_llama(
    path: str | os.PathLike,
    cfg=None,
    *,
    dtype=np.float32,
    quantize: bool = False,
    scan_layers: bool | None = None,
    strict: bool = True,
    materialize: bool = True,
):
    """Load a HF-layout Llama checkpoint into a TransformerLM param tree.

    Returns ``(cfg, params)``. ``cfg`` defaults to :func:`config_from_hf`
    on the directory's ``config.json``. Tensors stream one at a time:
    read -> cast to ``dtype`` -> transpose/reshape to the flax layout ->
    (optionally) quantize to int8 — the float checkpoint is never resident
    in full, matching the reference's 33-shards-through-bitsandbytes bound
    and :func:`.auto.load_quantized`'s RSS test.

    ``quantize=True`` emits the :class:`..ops.quant.Int8Dense` serving
    layout (``{'q', 'scale'}`` per matmul weight, norms/embeddings float)
    — serve with ``dataclasses.replace(cfg, quantized=True)``.
    ``scan_layers`` (default: follow ``cfg.scan_layers``) stacks the L
    per-layer subtrees under ``layers/block/...`` with a leading layer
    axis — the one-program layout — stacking int8 leaves
    (4x smaller than float), never the float originals.

    ``strict=True`` (default) fails loud if the checkpoint contains
    tensors the mapping did not consume — e.g. ``attention_bias=True``
    checkpoints store ``*.bias`` tensors TransformerLM has no slot for;
    dropping them silently would serve wrong logits. ``materialize=True``
    returns device-resident jax arrays (host-numpy leaves re-upload on
    every consuming launch — CLAUDE.md); pass ``False``
    to keep host numpy for tree surgery before placement.
    """
    ckpt = HFCheckpoint(path)
    if cfg is None:
        cfg = config_from_hf(path)
    if scan_layers is None:
        scan_layers = cfg.scan_layers
    consumed: set[str] = set()

    if quantize:
        from pytorch_distributed_training_tutorials_tpu.models.transformer import (
            _quantize_kernel,
        )
        from pytorch_distributed_training_tutorials_tpu.ops.quant import (
            quantize_int8,
        )

    def fetch(name: str, transform):
        consumed.add(name)
        arr = ckpt.get(name).astype(dtype)
        if transform is not None:
            arr = transform(arr)
        return arr

    def maybe_quant(our_path: tuple, leaf):
        if quantize and our_path[-1] == "kernel" and our_path[0] != "tok_emb":
            part = _quantize_kernel(our_path[-2], leaf, quantize_int8)
            return {"q": part["q"], "scale": part["scale"]}
        return leaf

    params: dict = {}
    _set(params, ("tok_emb", "embedding"),
         fetch("model.embed_tokens.weight", None))
    _set(params, ("final_norm", "scale"), fetch("model.norm.weight", None))
    if "lm_head.weight" in ckpt:
        head = fetch("lm_head.weight", lambda w: np.ascontiguousarray(w.T))
    else:  # tie_word_embeddings: reuse the embedding matrix
        head = np.ascontiguousarray(params["tok_emb"]["embedding"].T)
    q_head = maybe_quant(("lm_head", "kernel"), head)
    if isinstance(q_head, dict):
        params["lm_head"] = q_head
    else:
        _set(params, ("lm_head", "kernel"), q_head)

    layers = []
    for i in range(cfg.n_layers):
        block: dict = {}
        for our_path, hf_name, transform in _llama_layer_entries(i, cfg):
            leaf = maybe_quant(our_path, fetch(hf_name, transform))
            if isinstance(leaf, dict):
                _set(block, our_path[:-1] + ("q",), leaf["q"])
                _set(block, our_path[:-1] + ("scale",), leaf["scale"])
            else:
                _set(block, our_path, leaf)
        layers.append(block)

    if strict:
        leftover = sorted(set(ckpt.keys()) - consumed)
        if leftover:
            raise ValueError(
                f"{len(leftover)} checkpoint tensor(s) were not consumed "
                f"by the Llama mapping (first few: {leftover[:5]}) — "
                "loading would silently drop weights. Pass strict=False "
                "only if you know they are genuinely unused."
            )

    if scan_layers:
        import jax
        import jax.numpy as jnp

        params["layers"] = {
            "block": jax.tree_util.tree_map(
                lambda *leaves: jnp.stack([jnp.asarray(x) for x in leaves]),
                *layers,
            )
        }
    else:
        for i, block in enumerate(layers):
            params[f"block_{i}"] = block
    if materialize:
        from pytorch_distributed_training_tutorials_tpu.utils.tree import (
            device_materialize,
        )

        params = device_materialize(params)
    return cfg, params

"""Block ``gqa_swiglu``, the program's half: the program's
``TransformerLM`` (``scan_layers=True``) at a configuration's published
sizes, and the reference's weights in that model's parameter tree and back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib.program import module, put

# reference name -> (program module path under layers/block, axes split)
_LAYER_NAMES = {
    "attn_norm": ("attn_norm", None),
    "mlp_norm": ("mlp_norm", None),
    "wq": ("attn/q_proj", "out"),
    "wk": ("attn/k_proj", "out"),
    "wv": ("attn/v_proj", "out"),
    "wo": ("attn/o_proj", "in"),
    "w_gate": ("mlp/gate_proj", None),
    "w_up": ("mlp/up_proj", None),
    "w_down": ("mlp/down_proj", None),
}


def model(config: dict, mode: str, max_seq_len: int):
    """``TransformerLM`` at the configuration's published sizes with the
    mode's options from its file."""
    models = module("models")
    opts = config[mode]
    kw = dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(opts["compute_dtype"]), scan_layers=True,
    )
    if mode == "train":
        kw.update(remat=True, remat_policy=opts["remat_policy"])
        if opts["kernels"]:
            kw["attention_fn"] = module("ops.flash_attention").flash_attention
    else:
        kw["quantized"] = opts["weights_dtype"] == "int8"
        if "kv_cache_dtype" in opts:
            kw["kv_cache_dtype"] = jnp.dtype(opts["kv_cache_dtype"])
    return models.TransformerLM(models.TransformerConfig(**kw))


def to_program(tree: dict, shape) -> dict:
    """The reference layout as ``TransformerLM(scan_layers=True)`` names
    it. int8 leaves keep their arrays (no copy: the flattened 2-D kernel is
    the program's own layout); float32 projections take the program's
    (d, heads, head_dim) / (heads, head_dim, d) shapes."""
    hd = shape.head_dim
    out: dict = {}
    put(out, "tok_emb/embedding", tree["embed"])
    put(out, "final_norm/scale", tree["final_norm"])
    for name, leaf in tree["layers"].items():
        path, split = _LAYER_NAMES[name]
        base = f"layers/block/{path}"
        if isinstance(leaf, dict):
            put(out, base + "/q", leaf["q"])
            put(out, base + "/scale", leaf["scale"])
        elif leaf.ndim == 2:
            put(out, base + "/scale", leaf)
        else:
            if split == "out":
                leaf = leaf.reshape(*leaf.shape[:2], -1, hd)
            elif split == "in":
                leaf = leaf.reshape(leaf.shape[0], -1, hd, leaf.shape[-1])
            put(out, base + "/kernel", leaf)
    head = tree["head"]
    if isinstance(head, dict):
        put(out, "lm_head/q", head["q"])
        put(out, "lm_head/scale", head["scale"])
    else:
        put(out, "lm_head/kernel", head)
    return out


def from_program(tree) -> dict:
    """A program-layout tree of float leaves (parameters, a moment) in the
    reference layout, projections flattened to 2-D a layer."""
    tree = jax.tree_util.tree_map(lambda x: x, dict(tree))  # plain dicts
    block = tree["layers"]["block"]
    layers = {}
    for name, (path, split) in _LAYER_NAMES.items():
        node = block
        for p in path.split("/"):
            node = node[p]
        leaf = node["scale"] if split is None and "scale" in node else node["kernel"]
        if split == "out":
            leaf = leaf.reshape(*leaf.shape[:2], -1)
        elif split == "in":
            leaf = leaf.reshape(leaf.shape[0], -1, leaf.shape[-1])
        layers[name] = leaf
    return {
        "embed": tree["tok_emb"]["embedding"],
        "final_norm": tree["final_norm"]["scale"],
        "head": tree["lm_head"]["kernel"],
        "layers": layers,
    }

"""Block ``routed_swiglu``, the program's half (a test fixture): the
program's ``TransformerLM`` with ``moe_experts`` switched on, at a capacity
under which ``models/moe.py`` drops no token (``E / k``: room for every
token of a row at every expert), and the reference's weights in its tree.
Training in float32 weights only."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib.program import module, put

# reference name -> (path of the parameter under layers/block, axes split)
_NAMES = {
    "attn_norm": ("attn_norm/scale", None),
    "mlp_norm": ("mlp_norm/scale", None),
    "wq": ("attn/q_proj/kernel", "out"),
    "wk": ("attn/k_proj/kernel", "out"),
    "wv": ("attn/v_proj/kernel", "out"),
    "wo": ("attn/o_proj/kernel", "in"),
    "router": ("moe/router", None),
    "experts_gate": ("moe/w_gate", None),
    "experts_up": ("moe/w_up", None),
    "experts_down": ("moe/w_down", None),
}
_TOP = {"embed": "tok_emb/embedding", "final_norm": "final_norm/scale",
        "head": "lm_head/kernel"}


def model(config: dict, mode: str, max_seq_len: int):
    models = module("models")
    opts = config[mode]
    experts, top_k = config["num_experts"], config["num_experts_per_tok"]
    return models.TransformerLM(models.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(opts["compute_dtype"]), scan_layers=True,
        remat=True, remat_policy=opts["remat_policy"],
        moe_experts=experts, moe_top_k=top_k,
        moe_capacity_factor=experts / top_k,
    ))


def to_program(tree: dict, shape) -> dict:
    hd = shape.head_dim
    out: dict = {}
    for name, path in _TOP.items():
        put(out, path, tree[name])
    for name, leaf in tree["layers"].items():
        path, split = _NAMES[name]
        if split == "out":
            leaf = leaf.reshape(*leaf.shape[:2], -1, hd)
        elif split == "in":
            leaf = leaf.reshape(leaf.shape[0], -1, hd, leaf.shape[-1])
        put(out, "layers/block/" + path, leaf)
    return out


def _get(tree, path: str):
    for p in path.split("/"):
        tree = tree[p]
    return tree


def from_program(tree) -> dict:
    tree = jax.tree_util.tree_map(lambda x: x, dict(tree))  # plain dicts
    out = {name: _get(tree, path) for name, path in _TOP.items()}
    out["layers"] = {}
    for name, (path, split) in _NAMES.items():
        leaf = _get(tree["layers"]["block"], path)
        if split == "out":
            leaf = leaf.reshape(*leaf.shape[:2], -1)
        elif split == "in":
            leaf = leaf.reshape(leaf.shape[0], -1, leaf.shape[-1])
        out["layers"][name] = leaf
    return out

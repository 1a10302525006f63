"""The only place the benchmark touches the program: it builds the model,
the ``Trainer`` and the ``ServeEngine`` from a configuration's file, and
maps the benchmark's weights to the program's parameter tree.

From the program come the system under test and nothing else: no weights,
no data, no metric arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PKG = "pytorch_distributed_training_tutorials_tpu"


def _mod(name: str = ""):
    """A module of the program, imported when first needed."""
    import importlib

    return importlib.import_module(PKG + ("." + name if name else ""))


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


def _put(tree: dict, path: str, value) -> None:
    *parents, last = path.split("/")
    for p in parents:
        tree = tree.setdefault(p, {})
    tree[last] = value


def to_program(tree: dict, shape) -> dict:
    """The reference layout as ``TransformerLM(scan_layers=True)`` names
    it. int8 leaves keep their arrays (no copy: the flattened 2-D kernel is
    the program's own layout); float32 projections take the program's
    (d, heads, head_dim) / (heads, head_dim, d) shapes."""
    hd = shape.head_dim
    out: dict = {}
    _put(out, "tok_emb/embedding", tree["embed"])
    _put(out, "final_norm/scale", tree["final_norm"])
    for name, leaf in tree["layers"].items():
        path, split = _LAYER_NAMES[name]
        base = f"layers/block/{path}"
        if isinstance(leaf, dict):
            _put(out, base + "/q", leaf["q"])
            _put(out, base + "/scale", leaf["scale"])
        elif leaf.ndim == 2:
            _put(out, base + "/scale", leaf)
        else:
            if split == "out":
                leaf = leaf.reshape(*leaf.shape[:2], -1, hd)
            elif split == "in":
                leaf = leaf.reshape(leaf.shape[0], -1, hd, leaf.shape[-1])
            _put(out, base + "/kernel", leaf)
    head = tree["head"]
    if isinstance(head, dict):
        _put(out, "lm_head/q", head["q"])
        _put(out, "lm_head/scale", head["scale"])
    else:
        _put(out, "lm_head/kernel", head)
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


def check_same_structure(ours, theirs) -> None:
    """Fail loudly where the program's tree has another leaf, shape or
    type than the benchmark made."""
    a = {jax.tree_util.keystr(k): (v.shape, str(v.dtype))
         for k, v in jax.tree_util.tree_leaves_with_path(ours)}
    b = {jax.tree_util.keystr(k): (v.shape, str(v.dtype))
         for k, v in jax.tree_util.tree_leaves_with_path(theirs)}
    if a != b:
        diff = {k: (a.get(k), b.get(k)) for k in set(a) | set(b)
                if a.get(k) != b.get(k)}
        raise SystemExit(f"parameter trees differ (ours, program's): {diff}")


def model_config(config: dict, mode: str, max_seq_len: int):
    """``TransformerConfig`` at the configuration's published sizes with
    the mode's options from its file."""
    models = _mod("models")
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
            kw["attention_fn"] = _mod("ops.flash_attention").flash_attention
    else:
        kw["quantized"] = opts["weights_dtype"] == "int8"
        if "kv_cache_dtype" in opts:
            kw["kv_cache_dtype"] = jnp.dtype(opts["kv_cache_dtype"])
    return models.TransformerLM(models.TransformerConfig(**kw))


def strategy(name: str):
    return getattr(_mod("parallel"), name)(_mod().create_mesh())


def trainer(model, loader, config: dict, traffic: dict, strat, seed: int,
            on_step):
    import optax

    opts = config["train"]
    hyper = traffic["adamw"]
    adamw = _mod("ops.fused_optim").fused_adamw if opts["kernels"] else optax.adamw
    tx = adamw(
        hyper["learning_rate"], b1=hyper["b1"], b2=hyper["b2"],
        eps=hyper["eps"], weight_decay=hyper["weight_decay"],
    )
    return _mod("train").Trainer(
        model, loader, tx, strategy=strat,
        loss="fused_cross_entropy" if opts["kernels"] else "cross_entropy",
        seed=seed & 0x7FFFFFFF, quiet=True, on_step=on_step,
    )


def sharded_loader(arrays: tuple, batch: int, mesh, seed: int):
    data = _mod("data")
    return data.ShardedLoader(
        data.ArrayDataset(arrays), batch, mesh, batch_mode="global",
        seed=seed & 0x7FFFFFFF,
    )


def serve_engine(model, params, options: dict):
    return _mod("serve").ServeEngine(model, params, **options)


def request(prompt, max_new_tokens: int):
    return _mod("serve").Request(prompt=prompt, max_new_tokens=max_new_tokens)


def enable_compile_cache(path: str) -> None:
    """The benchmark's own fixed directory inside the checkout, whatever
    the machine's environment says (PR 25: the machine's directory gave
    back 11 of 17 programs)."""
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

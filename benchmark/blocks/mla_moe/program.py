"""Block ``mla_moe``, the program's half: the program's ``TransformerLM``
with latent attention, sandwich norms, leading dense layers and layers of
dropless routed experts as one chip's share, and the reference's weights in
that model's parameter tree. The reference lays its leaves out as the
program stores them, so :func:`to_program` only renames: no leaf is copied.
Serving only.

The layers are **unrolled** (``scan_layers=False``): one ``nn.scan`` takes
layers of one kind, and this model has a leading dense layer before its
layers of experts (``TransformerLM`` refuses the combination in words). A
scan would not pay here either: under it every Pallas call's weights are
first copied out of the stacked array (``lax.scan`` slices them, and a
custom call takes no slice in place), and with a few rows against 4.5 GB of
expert weights a step that copy was 40 % of the decode step on the chip
(PERF.md, PR 30), where the accepted cells pay 7-12 %.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.lib.program import module, put

# reference name -> (path under block_<i>, what the leaf is)
_ATTENTION = {
    "attn_norm": ("attn_norm", "norm"), "post_attn_norm": ("post_attn_norm", "norm"),
    "mlp_norm": ("mlp_norm", "norm"), "post_mlp_norm": ("post_mlp_norm", "norm"),
    "q_norm": ("attn/q_norm", "norm"), "kv_norm": ("attn/kv_norm", "norm"),
    "w_dq": ("attn/q_down", "dense"), "w_uq": ("attn/q_up", "dense"),
    "w_dkv": ("attn/kv_down", "dense"), "w_ukv": ("attn/kv_up", "dense"),
    "w_o": ("attn/o_proj", "dense"),
}
_NAMES = dict(
    _ATTENTION,
    w_gate=("mlp/gate_proj", "dense"), w_up=("mlp/up_proj", "dense"),
    w_down=("mlp/down_proj", "dense"),
    router=("moe/router", "bare"),
    experts_gate=("moe/w_gate", "bare"), experts_up=("moe/w_up", "bare"),
    experts_down=("moe/w_down", "bare"),
    shared_gate=("shared/gate_proj", "dense"), shared_up=("shared/up_proj", "dense"),
    shared_down=("shared/down_proj", "dense"),
)


def model(config: dict, mode: str, max_seq_len: int):
    """``TransformerLM`` at the configuration's sizes with the mode's
    options from its file; ``n_routed_experts`` is the experts held here,
    ``n_routed_experts_published`` the router's width."""
    models = module("models")
    opts = config[mode]
    kw = dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_dense_layers=config["first_k_dense_replace"],
        n_heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        sandwich_norm=bool(config["sandwich_norm"]),
        n_routed_experts=config["n_routed_experts_published"],
        experts_held=config["n_routed_experts"],
        expert_offset=config["expert_offset"],
        experts_per_token=config["num_experts_per_tok"],
        expert_d_ff=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        routed_scaling=float(config["routed_scaling_factor"]),
        dtype=jnp.dtype(opts["compute_dtype"]), scan_layers=False,
        quantized=opts["weights_dtype"] == "int8",
    )
    if "kv_cache_dtype" in opts:
        kw["kv_cache_dtype"] = jnp.dtype(opts["kv_cache_dtype"])
    return models.TransformerLM(models.TransformerConfig(**kw))


def _place(out: dict, base: str, leaf, what: str) -> None:
    if isinstance(leaf, dict):  # int8: the program's own {"q", "scale"}
        put(out, base + "/q", leaf["q"])
        put(out, base + "/scale", leaf["scale"])
    elif what == "norm":
        put(out, base + "/scale", leaf)
    elif what == "dense":
        put(out, base + "/kernel", leaf)
    else:
        put(out, base, leaf)


def to_program(tree: dict, shape) -> dict:
    """The reference's tree under ``TransformerLM``'s names; every leaf is
    the reference's own array."""
    out: dict = {}
    put(out, "tok_emb/embedding", tree["embed"])
    put(out, "final_norm/scale", tree["final_norm"])
    _place(out, "lm_head", tree["head"], "dense")
    for i in range(shape.num_hidden_layers):
        for name, leaf in tree[f"layer_{i:02d}"].items():
            path, what = _NAMES[name]
            _place(out, f"block_{i}/{path}", leaf, what)
    return out

"""Block ``falcon_h1``, the program's half: the program's ``TransformerLM``
with a Mamba-2 mixer beside attention in every block (``mamba_n_heads``,
``models/mamba2.py``) under its one layer scan, at a configuration's
published sizes and with its published multipliers (the model applies
them: :func:`to_program` scales nothing), and the reference's weights in
that model's parameter tree. The reference lays its leaves out as the
program stores them, so :func:`to_program` renames; what it makes anew are
the few small leaves the reference derives from the seed's draws
(``reference.drawn``: convolution taps, ``A_log``, ``dt_bias``) and, served,
the lookup table: the int8 embedding's values dequantized in the compute
type (``vocab_size x hidden_size x 2`` bytes in bfloat16: 2.67 GB at the
published sizes), in one fused pass. Serving only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.blocks.falcon_h1 import reference
from benchmark.lib.program import module, put

# reference name -> (path under layers/block, what the leaf is)
_LAYER_NAMES = {
    "input_norm": ("attn_norm/scale", "bare"),
    "pre_ff_norm": ("mlp_norm/scale", "bare"),
    "wq": ("attn/q_proj", "heads_out"),
    "wk": ("attn/k_proj", "heads_out"),
    "wv": ("attn/v_proj", "heads_out"),
    "wo": ("attn/o_proj", "heads_in"),
    "in_proj": ("mamba/in_proj", "dense"),
    "dt_proj": ("mamba/dt_proj", "dense"),
    "out_proj": ("mamba/out_proj", "dense"),
    "conv_weight": ("mamba/conv_weight", "bare"),
    "conv_bias": ("mamba/conv_bias", "bare"),
    "dt_bias": ("mamba/dt_bias", "bare"),
    "a_log": ("mamba/A_log", "bare"),
    "d_skip": ("mamba/D", "bare"),
    "ssm_norm": ("mamba/norm_scale", "bare"),
    "w_gate": ("mlp/gate_proj", "dense"),
    "w_up": ("mlp/up_proj", "dense"),
    "w_down": ("mlp/down_proj", "dense"),
}

_MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier",
)


def model(config: dict, mode: str, max_seq_len: int):
    """``TransformerLM`` at the configuration's sizes with the mode's
    options from its file."""
    models = module("models")
    if "mamba_n_heads" not in models.TransformerConfig.__dataclass_fields__:
        raise SystemExit(
            "this program cannot run block 'falcon_h1': its TransformerConfig "
            "has no mamba_n_heads (a Mamba-2 mixer beside attention in every "
            "block, models/mamba2.py, came with PR 36)")
    opts = config[mode]
    quantized = opts["weights_dtype"] == "int8"
    dtype = jnp.dtype(opts["compute_dtype"])
    kw = dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"], d_ff=config["intermediate_size"],
        max_seq_len=max_seq_len, rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        ssm_multipliers=tuple(float(m) for m in config["ssm_multipliers"]),
        mlp_multipliers=tuple(float(m) for m in config["mlp_multipliers"]),
        **{name: float(config[name]) for name in _MULTIPLIERS},
        dtype=dtype, scan_layers=True, quantized=quantized,
        # a served model keeps its lookup table in the compute type
        embedding_dtype=dtype if quantized else None,
    )
    if "kv_cache_dtype" in opts:
        kw["kv_cache_dtype"] = jnp.dtype(opts["kv_cache_dtype"])
    return models.TransformerLM(models.TransformerConfig(**kw))


def to_program(tree: dict, shape) -> dict:
    """The reference's tree under ``TransformerLM``'s names."""
    tree = reference.drawn(tree)
    hd = shape.head_dim
    out: dict = {}
    embed = tree["embed"]
    if isinstance(embed, dict):
        # the served lookup table: the embedding's values in the compute
        # type, in one pass (dequantized eagerly the float32 copy is 5.3 GB)
        embed = jax.jit(
            lambda q, scale: (q.astype(jnp.float32) * scale).astype(
                shape.compute_dtype)
        )(embed["q"], embed["scale"])
    put(out, "tok_emb/embedding", embed)
    put(out, "final_norm/scale", tree["final_norm"])
    for name, leaf in tree["layers"].items():
        path, what = _LAYER_NAMES[name]
        base = f"layers/block/{path}"
        if isinstance(leaf, dict):  # int8: the program's own {"q", "scale"}
            put(out, base + "/q", leaf["q"])
            put(out, base + "/scale", leaf["scale"])
        elif what == "bare":
            put(out, base, leaf)
        else:
            if what == "heads_out":  # (L, d, heads * hd) -> (L, d, heads, hd)
                leaf = leaf.reshape(*leaf.shape[:2], -1, hd)
            elif what == "heads_in":  # (L, heads * hd, d) -> (L, heads, hd, d)
                leaf = leaf.reshape(leaf.shape[0], -1, hd, leaf.shape[-1])
            put(out, base + "/kernel", leaf)
    head = tree["head"]
    if isinstance(head, dict):
        put(out, "lm_head/q", head["q"])
        put(out, "lm_head/scale", head["scale"])
    else:
        put(out, "lm_head/kernel", head)
    return out

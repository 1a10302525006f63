"""Block ``sambay``, the program's half: the program's ``TransformerLM`` laid
out by ``mb_per_layer`` (``models/sambay.py``: two layer scans of a period of
two blocks, the two middle layers between them) and the reference's weights
in that model's parameter tree. The reference lays its leaves out as the
program stores them, so :func:`to_program` renames; what it makes anew are
the few small leaves the reference derives from the seed's draws
(``reference.drawn``: convolution taps, ``A_log``, ``b_dt``, lambda vectors)
and the embedding, which is the head's transpose dequantized in the compute
type (``vocab_size x hidden_size x 2`` bytes in bfloat16: 1.02 GB at the
published sizes, held beside the int8 head). Serving only.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.blocks.sambay import reference
from benchmark.lib.program import module, put

# reference name -> (path under the block, what the leaf is)
_BLOCK = {
    "ln1": ("ln1", "ln"), "ln2": ("ln2", "ln"),
    "gate_up": ("mlp/gate_up_proj", "dense"), "down": ("mlp/down_proj", "dense"),
}
_MAMBA = dict(
    _BLOCK,
    in_proj=("mixer/in_proj", "dense"), x_proj=("mixer/x_proj", "dense"),
    dt_proj=("mixer/dt_proj", "dense"), dt_bias=("mixer/dt_proj/bias", "bare"),
    out_proj=("mixer/out_proj", "dense"),
    conv_weight=("mixer/conv_weight", "bare"), conv_bias=("mixer/conv_bias", "bare"),
    a_log=("mixer/A_log", "bare"), d_skip=("mixer/D", "bare"),
)
_ATTENTION = dict(
    _BLOCK,
    qkv_proj=("attn/qkv_proj", "dense"), qkv_bias=("attn/qkv_proj/bias", "bare"),
    q_proj=("attn/q_proj", "dense"), q_bias=("attn/q_proj/bias", "bare"),
    o_proj=("attn/o_proj", "dense"), o_bias=("attn/o_proj/bias", "bare"),
    subln=("attn/subln", "bare"),
    **{name: ("attn/" + name, "bare") for name in reference.LAMBDAS},
)
_GMU = dict(
    _BLOCK,
    in_proj=("mixer/in_proj", "dense"), out_proj=("mixer/out_proj", "dense"),
)


def model(config: dict, mode: str, max_seq_len: int):
    """``TransformerLM`` at the configuration's sizes with the mode's
    options from its file."""
    models = module("models")
    if "mb_per_layer" not in models.TransformerConfig.__dataclass_fields__:
        raise SystemExit(
            "this program cannot run block 'sambay': its TransformerConfig "
            "has no mb_per_layer (layers with recurrent state, "
            "models/sambay.py, came with PR 34)")
    opts = config[mode]
    kw = dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=max_seq_len,
        norm_eps=float(config["layer_norm_eps"]),
        mb_per_layer=config["mb_per_layer"],
        sliding_window=config["sliding_window"],
        mamba_d_state=config["mamba_d_state"], mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"], mamba_dt_rank=config["mamba_dt_rank"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=jnp.dtype(opts["compute_dtype"]), scan_layers=True,
        quantized=opts["weights_dtype"] == "int8",
    )
    if "kv_cache_dtype" in opts:
        kw["kv_cache_dtype"] = jnp.dtype(opts["kv_cache_dtype"])
    return models.TransformerLM(models.TransformerConfig(**kw))


def _place(out: dict, base: str, names: dict, group: dict) -> None:
    for name, leaf in group.items():
        path, what = names[name]
        at = f"{base}/{path}"
        if what == "ln":
            put(out, at + "/scale", leaf["scale"])
            put(out, at + "/bias", leaf["bias"])
        elif isinstance(leaf, dict):  # int8: the program's own {"q", "scale"}
            put(out, at + "/q", leaf["q"])
            put(out, at + "/scale", leaf["scale"])
        elif what == "dense":
            put(out, at + "/kernel", leaf)
        else:
            put(out, at, leaf)


def to_program(tree: dict, shape) -> dict:
    """The reference's tree under ``TransformerLM``'s names."""
    tree = reference.drawn(tree)
    out: dict = {}
    head = tree["head"]
    if isinstance(head, dict):
        put(out, "lm_head/q", head["q"])
        put(out, "lm_head/scale", head["scale"])
        # the served lookup table: the head's values in the compute type
        head = (head["q"].astype(jnp.float32) * head["scale"]).astype(
            shape.compute_dtype)
    put(out, "tok_emb/embedding", head.T)
    put(out, "final_norm/scale", tree["final_norm"]["scale"])
    put(out, "final_norm/bias", tree["final_norm"]["bias"])
    _place(out, "layers_a/mamba_block", _MAMBA, tree["layers_a"]["mamba"])
    _place(out, "layers_a/window_block", _ATTENTION, tree["layers_a"]["window"])
    _place(out, f"block_{shape.half}", _MAMBA, tree["mid_mamba"])
    _place(out, f"block_{shape.half + 1}", _ATTENTION, tree["mid_full"])
    _place(out, "layers_b/gmu_block", _GMU, tree["layers_b"]["gmu"])
    _place(out, "layers_b/cross_block", _ATTENTION, tree["layers_b"]["cross"])
    return out

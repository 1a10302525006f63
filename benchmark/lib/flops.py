"""Analytic operation counts from a configuration's published sizes: what
the mathematics needs, whatever computes it. Recomputed operations
(rematerialization, a flash backward's second pass over the scores) are
not counted. PaLM's convention (appendix B): 2 operations a multiply-add,
6 N a trained token for N matrix parameters, and 12 L H hd S for the
attention scores and their weighted sums over a full S x S square; serving
counts the causal triangle it really needs.
"""

from __future__ import annotations


def matmul_params(shape) -> dict:
    """Matrix parameters that multiply a token's activations: a layer's
    projections and feed-forward, and the output head. The embedding is a
    lookup and does no multiplication."""
    d, ff = shape.hidden_size, shape.intermediate_size
    q = shape.num_attention_heads * shape.head_dim
    kv = shape.num_key_value_heads * shape.head_dim
    layer = d * (q + 2 * kv) + q * d + 3 * d * ff
    return {
        "layer": layer,
        "layers": layer * shape.num_hidden_layers,
        "head": d * shape.vocab_size,
        "embedding": d * shape.vocab_size,
        "norms": d * (2 * shape.num_hidden_layers + 1),
    }


def total_params(shape) -> int:
    p = matmul_params(shape)
    return p["layers"] + p["head"] + p["embedding"] + p["norms"]


def train_flops_per_token(shape, seq_len: int) -> float:
    """Forward and backward of one token in a sequence of ``seq_len``."""
    p = matmul_params(shape)
    attn = (12 * shape.num_hidden_layers * shape.num_attention_heads
            * shape.head_dim * seq_len)
    return 6.0 * (p["layers"] + p["head"]) + attn


def serve_flops(shape, prompt_len: int, new_tokens: int) -> float:
    """One request: its prompt and all but the last generated token pass
    through the layers, each attending to what precedes it; the head is
    applied once for each generated token."""
    p = matmul_params(shape)
    through = prompt_len + new_tokens - 1
    attn = (4 * shape.num_hidden_layers * shape.num_attention_heads
            * shape.head_dim * through * (through + 1) / 2)
    return 2.0 * p["layers"] * through + 2.0 * p["head"] * new_tokens + attn

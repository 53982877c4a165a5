"""What the engine's steps need for the tokens they processed.

A token through the layers: 2 operations a matmul parameter (q, k, v, o,
gate, up, down). Attention over its context of c positions (itself
included): 4*c*h a layer (QK^T and PV). The head, 2*h*V, only for a token
whose logits are sampled. Padding tokens and the padding row need nothing.
"""
from __future__ import annotations


def flops(model: dict, stats: dict) -> float:
    """stats: `tokens` processed, `context` = the sum over them of the
    positions each attends (itself included), `sampled` tokens."""
    h, f = model["hidden_size"], model["intermediate_size"]
    kv = model["num_key_value_heads"] * (h // model["num_attention_heads"])
    n_layers = model["num_hidden_layers"]
    layer = 2 * h * h + 2 * h * kv + 3 * h * f
    return (2.0 * n_layers * layer * stats["tokens"]
            + 4.0 * h * n_layers * stats["context"]
            + 2.0 * h * model["vocab_size"] * stats["sampled"])

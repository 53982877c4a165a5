"""What one training step of the dense decoder needs, a token.

Matmul parameters only: q, k, v, o, gate, up, down of each layer and the
head; the embedding lookup is no matmul. Forward and backward are 6
operations a parameter a token. Attention is counted CAUSAL: the lower
triangle of QK^T and of PV, forward 2*S*h a token a layer, backward twice
that, 6*S*h in all. Recomputed operations are not counted.
"""
from __future__ import annotations


def matmul_params(model: dict) -> int:
    h, f = model["hidden_size"], model["intermediate_size"]
    kv = model["num_key_value_heads"] * (h // model["num_attention_heads"])
    layer = 2 * h * h + 2 * h * kv + 3 * h * f
    return model["num_hidden_layers"] * layer + h * model["vocab_size"]


def flops(model: dict, stats: dict) -> float:
    """Operations for `stats["tokens"]` tokens in sequences of
    `stats["seq"]`."""
    per_token = 6.0 * matmul_params(model) + 6.0 * stats["seq"] \
        * model["hidden_size"] * model["num_hidden_layers"]
    return per_token * stats["tokens"]

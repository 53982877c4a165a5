"""What `alt_lm`'s engine steps need for the tokens they processed, from
its own size keys: 2 operations a matmul parameter a token, attention over
each token's context, the head for a sampled token."""
from __future__ import annotations


def flops(model: dict, stats: dict) -> float:
    d, f, n_layer = model["d_model"], model["d_ff"], model["n_layer"]
    kv = model["n_kv_head"] * (d // model["n_head"])
    layer = 2 * d * d + 2 * d * kv + 3 * d * f
    return (2.0 * n_layer * layer * stats["tokens"]
            + 4.0 * d * n_layer * stats["context"]
            + 2.0 * d * model["n_vocab"] * stats["sampled"])

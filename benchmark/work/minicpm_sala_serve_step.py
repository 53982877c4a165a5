"""What the MiniCPM-SALA engine's steps need for the tokens they processed,
from the configuration's own keys (`configs/minicpm-sala-serve-d8.json`).

A token through the layers: 2 operations a matmul parameter of each layer
(the mixer's q, k, v, gate and output projections and the MLP's three);
the lightning recurrence, 4 x d x d a head a token (the state's update and
its read); attention, in the block-sparse layers only, over the keys a
query REALLY attends, 4 x keys x heads x head_dim: all `n` of its context
up to `dense_len`, past it the keys of the blocks it selects, counted as
`init_blocks` and `topk` blocks and the local window. The runner hands over
the SUM of the contexts, not each: the attended keys are taken as tokens x
min(mean context, the selection's keys), which by concavity is no less
than the true sum (one long prompt's queries under `dense_len` attend fewer)
and overstates this term by under a fifth, the whole step by under a
hundredth. The head, 2 x hidden x vocabulary, for a token whose logits are
sampled. The selection's own scores (a query against one compressed key
for every 16 positions) are the mechanism's cost, not the model's work, and
are not counted. Padding needs nothing.
"""
from __future__ import annotations


def flops(model: dict, stats: dict) -> float:
    """stats: `tokens` processed, `context` = the sum over them of the
    positions each could attend (itself included), `sampled` tokens."""
    h, f = model["hidden_size"], model["intermediate_size"]
    hq, hkv, d = (model["num_attention_heads"],
                  model["num_key_value_heads"], model["head_dim"])
    lh, ld = model["lightning_nh"], model["lightning_head_dim"]
    sp = model["assumed"]["sparse_config"]
    n_sparse = model["mixer_types"].count("minicpm4")
    n_linear = model["mixer_types"].count("lightning-attn")
    mlp = 2.0 * 3 * h * f
    sparse = 2.0 * (h * (hq + 2 * hkv) * d + 2 * hq * d * h) + mlp
    linear = 2.0 * (5 * h * lh * ld) + 4.0 * lh * ld * ld + mlp
    tokens = stats["tokens"]
    chosen = (sp["init_blocks"] + sp["topk"]) * sp["block_size"] \
        + sp["window_size"]
    attended = tokens * min(stats["context"] / tokens, chosen) \
        if tokens else 0.0
    return ((n_sparse * sparse + n_linear * linear) * tokens
            + 4.0 * hq * d * n_sparse * attended
            + 2.0 * h * model["vocab_size"] * stats["sampled"])

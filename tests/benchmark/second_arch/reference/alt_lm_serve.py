"""Plain reference of `alt_lm`: the dense decoder's teacher-forced pass,
read from this architecture's own size keys (rope base among them)."""
from __future__ import annotations

from benchmark.reference import llama_serve


def logits_at(weights, tokens, first, model, precision="f32", pad_to=None):
    return llama_serve.logits_at(weights, tokens, first, {
        "num_attention_heads": model["n_head"],
        "num_key_value_heads": model["n_kv_head"],
        "num_hidden_layers": model["n_layer"],
        "intermediate_size": model["d_ff"],
        "rms_norm_eps": model["norm_eps"],
        "rope_theta": model["rope_base"]}, precision, pad_to)

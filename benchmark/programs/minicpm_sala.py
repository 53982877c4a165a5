"""`models/minicpm_sala.MiniCPMSala` under `ServingEngine.from_model`:
block-sparse attention layers (pages, the selector's compressed-key cache
beside them) and lightning attention layers (a row slot), one pipeline
stage's layers of the published depth (the configuration's `deployment`).
The weights are made in the served dtype, once: no float32 build."""
from __future__ import annotations

from .. import weights
from ..harness import BenchmarkError

# what the program computes as published, and nothing else
_AS_BUILT = {"attn_use_rope": False, "lightning_use_rope": True,
             "qk_norm": True, "use_output_gate": True,
             "use_output_norm": True, "attn_use_output_gate": True,
             "attention_bias": False, "tie_word_embeddings": False,
             "hidden_act": "silu", "lightning_scale": "1/sqrt(d)"}


def build_engine(config: dict, seed: int):
    """(model, engine, weight shapes); refuses what the program does not
    run as the configuration states it."""
    from paddle_tpu.inference.serving import (PagedServingConfig,
                                              ServingEngine)
    try:
        from paddle_tpu.models.minicpm_sala import (MiniCPMSala,
                                                    MiniCPMSalaSpec)
    except ImportError as e:
        raise BenchmarkError(
            "this checkout's program has no models/minicpm_sala.py (a "
            f"commit from before the configuration): {e}") from e

    for key, built in _AS_BUILT.items():
        if config[key] != built:
            raise BenchmarkError(
                f"models/minicpm_sala.py computes {key} = {built!r} only; "
                f"the configuration states {config[key]!r}")
    s, sparse = config["serving"], config["assumed"]["sparse_config"]
    if len(config["mixer_types"]) != config["num_hidden_layers"]:
        raise BenchmarkError("mixer_types and num_hidden_layers disagree")
    if s["block_size"] != sparse["block_size"]:
        raise BenchmarkError(
            "a selection block is one page: serving.block_size "
            f"{s['block_size']} against sparse block_size "
            f"{sparse['block_size']}")
    if s["max_blocks_per_seq"] * s["block_size"] \
            > config["max_position_embeddings"]:
        raise BenchmarkError("a context past the published positions")
    spec = MiniCPMSalaSpec.from_config(
        config, published_layers=config.get("published", {}).get(
            "num_hidden_layers", config["num_hidden_layers"]),
        chunk_size=s["chunk_size"], dtype=s["dtype"], **sparse)
    shapes = spec.param_shapes()
    model = MiniCPMSala(spec, weights.make_like(shapes, config, seed,
                                                donate=False))
    scfg = PagedServingConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=len(spec.mixer_types),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        ffn_size=config["intermediate_size"],
        block_size=s["block_size"], num_blocks=s["num_blocks"],
        max_batch=s["max_batch"],
        max_blocks_per_seq=s["max_blocks_per_seq"],
        token_budget=s["token_budget"], dtype=s["dtype"])
    engine = ServingEngine.from_model(model, scfg, seed=seed & 0x7FFFFFFF)
    return model, engine, shapes

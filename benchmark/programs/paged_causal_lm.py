"""`PagedCausalLM` under `ServingEngine.from_model`: the dense decoder
(RMSNorm, grouped-query attention, SwiGLU) on a paged KV cache."""
from __future__ import annotations

from .. import weights
from ..harness import BenchmarkError


def build_engine(config: dict, seed: int):
    """The program under test, as a deployment builds it: the model cast
    to the serving dtype (no float32 master), the benchmark's weights
    written into it, the engine over it."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              ServingEngine)
    from paddle_tpu.jit import functional as FB

    if config["rope_theta"] != 10000.0 or config["rms_norm_eps"] != 1e-6:
        raise BenchmarkError(
            "PagedCausalLM hard-codes rope base 10000 and RMSNorm epsilon "
            "1e-6; the configuration states another")
    s = config["serving"]
    if s["max_blocks_per_seq"] * s["block_size"] > config["sliding_window"]:
        raise BenchmarkError("the engine has no sliding-window attention")
    scfg = PagedServingConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        ffn_size=config["intermediate_size"], block_size=s["block_size"],
        num_blocks=s["num_blocks"], max_batch=s["max_batch"],
        max_blocks_per_seq=s["max_blocks_per_seq"],
        token_budget=s["token_budget"], dtype=s["dtype"])
    paddle.seed(seed & 0x7FFFFFFF)
    model = PagedCausalLM(scfg)
    model.eval()
    if s["dtype"] != "float32":
        model.to(dtype=s["dtype"])
    mine = weights.make_like(FB.current_params(model), config, seed,
                             donate=True)
    FB.write_back(model, mine)
    shapes = {k: (a.shape, a.dtype) for k, a in mine.items()}
    del mine
    engine = ServingEngine.from_model(model, scfg, seed=seed & 0x7FFFFFFF)
    return model, engine, shapes

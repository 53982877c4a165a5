"""A second architecture, for the test of the seam: the paged decoder with
the rope base its configuration states, under size keys of its own. What is
proved is that the harness finds it by name, not a model."""
from __future__ import annotations

import jax.numpy as jnp

from benchmark import weights


def build_engine(config: dict, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              ServingEngine)
    from paddle_tpu.jit import functional as FB

    class AltLM(PagedCausalLM):
        def _rope_table(self, positions):
            half = self.cfg.head_dim // 2
            inv = 1.0 / (config["rope_base"] ** (
                jnp.arange(half, dtype=jnp.float32) * 2.0
                / self.cfg.head_dim))
            ang = positions[..., None].astype(jnp.float32) * inv
            return jnp.cos(ang), jnp.sin(ang)

    scfg = PagedServingConfig(
        vocab_size=config["n_vocab"], hidden_size=config["d_model"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        num_kv_heads=config["n_kv_head"], ffn_size=config["d_ff"],
        **config["engine"])
    paddle.seed(seed & 0x7FFFFFFF)
    model = AltLM(scfg)
    model.eval()
    mine = weights.make_like(FB.current_params(model), config, seed,
                             donate=True)
    FB.write_back(model, mine)
    shapes = {k: (a.shape, a.dtype) for k, a in mine.items()}
    del mine
    engine = ServingEngine.from_model(model, scfg, seed=seed & 0x7FFFFFFF)
    return model, engine, shapes

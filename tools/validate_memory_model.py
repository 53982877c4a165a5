"""Validate the auto-tuner memory model against XLA's own accounting.

Compiles (AOT — no execution needed) the AdamW train step of a stack of
Llama-2-13B-dimension decoder blocks and compares
`auto_tuner.estimate_memory_bytes` against the compiled executable's
argument + temp bytes from `compiled.memory_analysis()`.

Usage: python tools/validate_memory_model.py [--small]
  --small: debug dims (runs anywhere, including the CPU backend)

Reference analog: the reference's tuner validates its memory model by
running trial jobs (distributed/auto_tuner/cost_model.py + recorder);
XLA's static memory analysis gives the same signal without burning chip
time.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp


def build_block_step(hidden, inter, heads, seq, batch, layers, remat):
    """The AdamW train step over `layers` stacked decoder blocks at the
    given dims. Returns (step_fn, blocks, opt_state, x, n_block_params)."""
    from paddle_tpu.models import llama
    from paddle_tpu.models.llama import _block

    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=hidden, intermediate_size=inter,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=heads, max_position_embeddings=seq,
        dtype="bfloat16", recompute=remat)

    params = jax.jit(
        lambda k: llama.init_stacked_params(cfg, k))(jax.random.key(0))
    blocks = params["blocks"]
    n_blk = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(blocks))
    opt = {"m": jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                             blocks),
           "v": jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                             blocks)}

    def step(blocks, opt, x):
        def loss_of(bl):
            def body(c, lp):
                return _block(lp, c, cfg), None

            bf = jax.checkpoint(body) if remat else body
            y, _ = jax.lax.scan(bf, x, bl)
            return jnp.sum(y.astype(jnp.float32)) * 1e-6

        loss, grads = jax.value_and_grad(loss_of)(blocks)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        def upd(p, g, m, v):
            m = 0.9 * m + 0.1 * g
            v = 0.95 * v + 0.05 * g * g
            return ((p.astype(jnp.float32)
                     - 3e-4 * m / (jnp.sqrt(v) + 1e-8)).astype(p.dtype),
                    m, v)

        out = jax.tree.map(upd, blocks, grads, opt["m"], opt["v"])

        def pick(i):
            return jax.tree.map(lambda o: o[i], out,
                                is_leaf=lambda x: isinstance(x, tuple))

        return pick(0), {"m": pick(1), "v": pick(2)}, loss

    x = jax.random.normal(jax.random.key(1), (batch, seq, hidden),
                          jnp.bfloat16)
    return step, blocks, opt, x, n_blk


def block_step_memory(hidden, inter, heads, seq, batch, layers, remat):
    """(predicted_bytes, measured_bytes, n_block_params) for the AdamW
    step of `layers` stacked decoder blocks at the given dims."""
    from paddle_tpu.distributed.auto_tuner import (TunerCfg,
                                                   estimate_memory_bytes)

    step, blocks, opt, x, n_blk = build_block_step(
        hidden, inter, heads, seq, batch, layers, remat)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        blocks, opt, x).compile()
    ma = compiled.memory_analysis()
    measured = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    predicted = estimate_memory_bytes(
        TunerCfg(1, 1, 1, 1, 1, batch, remat), n_blk, hidden, layers, seq)
    return predicted, measured, n_blk


def main():
    small = "--small" in sys.argv
    if small:
        grid = [dict(hidden=256, inter=688, heads=4, seq=512,
                     batch=b, layers=l, remat=rc)
                for b in (1, 2) for l in (1, 2) for rc in (True, False)]
    else:
        grid = [dict(hidden=5120, inter=13824, heads=40, seq=4096,
                     batch=b, layers=l, remat=rc)
                for (b, l, rc) in ((1, 1, True), (2, 1, True),
                                   (4, 1, True), (1, 2, True),
                                   (1, 1, False), (2, 1, False),
                                   (1, 2, False))]
    worst = 0.0
    for g in grid:
        pred, meas, n = block_step_memory(**g)
        ratio = pred / meas
        worst = max(worst, abs(1 - ratio))
        print(f"{g}: predicted {pred/1e9:.3f} GB, measured "
              f"{meas/1e9:.3f} GB, ratio {ratio:.3f}")
    print(f"worst |1-ratio|: {worst:.3f}")
    return worst


if __name__ == "__main__":
    main()

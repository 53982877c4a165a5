"""Sampler-internals ablation (decode bottleneck hunt, VERDICT r5).

The r5 decode ablation showed the top-k sampler scan costs ~7.5 ms of
the 10.26 ms bs-16 decode step. This times each sampler ingredient in a
16-step scan ended by a sync (device_get of a scalar). Prints one JSON
line.
"""
import json
import sys
import time

import jax
import jax.numpy as jnp

B1, V, C, WIN = 17, 32000, 128, 16


def timed(fn, n=3):
    jax.device_get(jnp.sum(fn()))  # warm/compile + sync
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.device_get(jnp.sum(fn()))
        best = min(best, time.perf_counter() - t0)
    return best


def scan_of(row_fn):
    """16-step scan of vmap(row_fn) over [B1, V] logits."""
    def run(lg):
        def body(c, j):
            out = jax.vmap(lambda l: row_fn(l, j))(lg + c[:, None] * 0)
            return out.astype(jnp.int32), out
        _, ys = jax.lax.scan(body, jnp.zeros((B1,), jnp.int32),
                             jnp.arange(WIN))
        return ys
    return jax.jit(run)


def main():
    stages = set(sys.argv[1:]) or {"argmax", "topk", "approx", "gumbelV",
                                   "full", "approx_full"}
    key = jax.random.key(0)
    lg = jax.device_put(jax.random.normal(key, (B1, V), jnp.float32))
    res = {}
    base = jax.random.key(0)

    if "argmax" in stages:
        dt = timed(lambda: scan_of(lambda l, j: jnp.argmax(l))(lg))
        res["argmax_ms_per_step"] = round(dt / WIN * 1e3, 3)

    if "topk" in stages:
        def row(l, j):
            vals, idx = jax.lax.top_k(l, C)
            return idx[0]
        dt = timed(lambda: scan_of(row)(lg))
        res["topk_ms_per_step"] = round(dt / WIN * 1e3, 3)

    if "approx" in stages:
        def row(l, j):
            vals, idx = jax.lax.approx_max_k(l, C)
            return idx[0]
        dt = timed(lambda: scan_of(row)(lg))
        res["approx_topk_ms_per_step"] = round(dt / WIN * 1e3, 3)

    if "gumbelV" in stages:
        def row(l, j):
            g = jax.random.gumbel(jax.random.fold_in(base, j), (V,),
                                  jnp.float32)
            return jnp.argmax(l + g)
        dt = timed(lambda: scan_of(row)(lg))
        res["gumbel_fullV_ms_per_step"] = round(dt / WIN * 1e3, 3)

    if "full" in stages:
        # the current _sample_topk_core chain
        def row(l, j):
            lt = l / 0.8
            vals, idx = jax.lax.top_k(lt, C)
            keep = jnp.arange(C) < 50
            pr = jax.nn.softmax(jnp.where(keep, vals, -jnp.inf))
            keep = keep & ((jnp.cumsum(pr) - pr) < 0.95)
            g = jax.random.gumbel(jax.random.fold_in(base, j), (V,),
                                  jnp.float32)
            win = jnp.argmax(jnp.where(keep, vals, -jnp.inf) + g[idx])
            return idx[win]
        dt = timed(lambda: scan_of(row)(lg))
        res["current_chain_ms_per_step"] = round(dt / WIN * 1e3, 3)

    if "approx_full" in stages:
        # candidate chain with approx_max_k + per-candidate-id gumbel
        def row(l, j):
            lt = l / 0.8
            vals, idx = jax.lax.approx_max_k(lt, C)
            keep = jnp.arange(C) < 50
            pr = jax.nn.softmax(jnp.where(keep, vals, -jnp.inf))
            keep = keep & ((jnp.cumsum(pr) - pr) < 0.95)
            kj = jax.random.fold_in(base, j)
            bits = jax.vmap(
                lambda t: jax.random.bits(jax.random.fold_in(kj, t)))(idx)
            u = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
            g = -jnp.log(-jnp.log(jnp.maximum(u, 1e-20)))
            win = jnp.argmax(jnp.where(keep, vals, -jnp.inf) + g)
            return idx[win]
        dt = timed(lambda: scan_of(row)(lg))
        res["approx_chain_ms_per_step"] = round(dt / WIN * 1e3, 3)

    res["device"] = str(getattr(jax.devices()[0], "device_kind", ""))
    print(json.dumps(res))


if __name__ == "__main__":
    main()

"""Decode-step ablation on the real chip (VERDICT r4 weak #1).

Times each component of the bs-16 flagship decode step in isolation:
  full      — the engine's decode_run window (reproduces BENCH step_ms)
  greedy    — same window with greedy sampling (isolates the sampler)
  no_attn   — block_multihead_attention stubbed to a pass-through
              (isolates the paged-cache gather + attention math)
  weights   — bare 16-layer matmul stack on T=16 tokens in a 16-step
              scan (the weight-streaming floor as XLA actually runs it)
  sampler   — 16-step scan of the top-k sampler alone on [17, 32000]

Run on an idle host. Prints one JSON line.
"""
import functools
import json
import time

import numpy as np
import jax
import jax.numpy as jnp


def _sync(out):
    """End the timing on the host: fetch a scalar reduction of the
    result (equivalent to block_until_ready on a directly attached
    chip)."""
    leaves = [x for x in jax.tree_util.tree_leaves(out)
              if hasattr(x, "dtype")]
    if leaves:   # engine paths sync internally (np.asarray of samples)
        jax.device_get(jnp.sum(leaves[-1].astype(jnp.float32)))


def timed(fn, n=2):
    _sync(fn())  # warm/compile
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn()
        _sync(out)
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    import sys
    stages = set(sys.argv[1:]) or {"full", "greedy", "no_attn", "weights",
                                   "sampler"}
    import paddle_tpu as paddle
    from paddle_tpu.inference import serving as S

    # prompt 64 (not the bench's 128): per-step cost is STATIC-shape
    # (gather + attention always run at max_seq), so a shorter prompt
    # changes nothing per-step but leaves max_new room for the window
    # sweep inside the 6-page/seq budget
    B, win, prompt_len = 16, 32, 64
    paddle.seed(0)
    cfg = S.PagedServingConfig.llama_1b(max_batch=B, num_blocks=B * 6 + 16)
    model = None
    if stages & {"full", "greedy", "no_attn"}:
        with jax.default_device(jax.devices("cpu")[0]):
            model = S.PagedCausalLM(cfg)
        model.eval()
    rng = np.random.RandomState(0)
    sp = S.SamplingParams(temperature=0.8, top_k=50, top_p=0.95)

    def mk_engine(m):
        eng = S.ServingEngine.from_model(m, cfg, seed=0)
        for _ in range(B):
            eng.add_request(list(rng.randint(1, cfg.vocab_size, prompt_len)),
                            max_new_tokens=126, sampling=sp)
        while any(r.length - r.cached > 1 for r in eng.pending()):
            eng.step()
        return eng

    res = {}

    # -- full window sweep ------------------------------------------------
    # One decode_run(n) is one dispatch + one sync, so a single window
    # size conflates per-step cost with per-window overhead. Sweep n and
    # fit the slope: per_step = the device time, intercept =
    # dispatch+sync overhead per window.
    if "full" in stages:
        eng = mk_engine(model)
        eng.decode_run(2)  # warm
        pts = []
        for n in (8, 32):
            dt = timed(lambda: eng.decode_run(n) or eng._kc)
            pts.append((n, dt))
            res[f"full_win{n}_ms_per_step"] = round(dt / n * 1e3, 3)
        (n1, d1), (n2, d2) = pts
        slope = (d2 - d1) / (n2 - n1)
        res["full_ms_per_step_slope"] = round(slope * 1e3, 3)
        res["full_window_overhead_ms"] = round((d1 - slope * n1) * 1e3, 2)

    # -- greedy window (no top-k sampler) ---------------------------------
    if "greedy" in stages:
        eng2 = S.ServingEngine.from_model(model, cfg, seed=0)
        for _ in range(B):
            eng2.add_request(
                list(rng.randint(1, cfg.vocab_size, prompt_len)),
                max_new_tokens=126, sampling=S.GREEDY)
        while any(r.length - r.cached > 1 for r in eng2.pending()):
            eng2.step()
        eng2.decode_run(2)
        dt = timed(lambda: eng2.decode_run(win) or eng2._kc)
        res["greedy_ms_per_step"] = round(dt / win * 1e3, 3)

    # -- no-attention window ---------------------------------------------
    if "no_attn" in stages:
        from paddle_tpu.incubate.nn import functional as IF
        orig = IF.block_multihead_attention

        def stub(qkv, kc, vc, *a, layer_idx=None, **kw):
            def fn(q):
                D = cfg.head_dim
                HQ, HKV = cfg.num_heads, cfg.num_kv_heads
                return q[:, :HQ * D]
            from paddle_tpu.core.dispatch import apply
            return apply(fn, qkv, op_name="attn_stub"), qkv, kc, vc

        IF.block_multihead_attention = stub
        try:
            with jax.default_device(jax.devices("cpu")[0]):
                model2 = S.PagedCausalLM(cfg)
            model2.eval()
            eng3 = mk_engine(model2)
            eng3.decode_run(2)
            dt = timed(lambda: eng3.decode_run(win) or eng3._kc)
            res["no_attn_ms_per_step"] = round(dt / win * 1e3, 3)
        finally:
            IF.block_multihead_attention = orig

    if not stages & {"weights", "sampler"}:
        dev = jax.devices()[0]
        res["device"] = str(getattr(dev, "device_kind", dev))
        print(json.dumps(res))
        return

    # -- bare weight-streaming scan --------------------------------------
    h, f, V = cfg.hidden_size, cfg.ffn_size, cfg.vocab_size
    L = cfg.num_layers
    key = jax.random.key(0)
    if "weights" in stages:
        Ws = _make_ws(cfg, key)

        # Ws must be jit ARGUMENTS: closed-over they become HLO literal
        # constants and the remote compile ships 1.77 GB of proto
        def wstep(ws, x, _):
            def layer(xc, w):
                qkvw, projw, guw, downw = w
                a = xc @ qkvw
                xc = xc + a[:, :h] @ projw
                g = xc @ guw
                xc = xc + (jax.nn.silu(g[:, :f]) * g[:, f:]) @ downw
                return xc, None
            x, _ = jax.lax.scan(layer, x,
                                (ws["qkv"], ws["proj"], ws["gu"],
                                 ws["down"]))
            logits = x @ ws["head"]
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return ws["emb"][nxt], nxt

        x0 = jnp.zeros((B, h), jnp.bfloat16)
        pts = []
        for n in (win, 4 * win):
            wrun = jax.jit(functools.partial(
                lambda ln, ws, x: jax.lax.scan(
                    lambda c, u: wstep(ws, c, u), x, None, length=ln), n))
            dt = timed(lambda: wrun(Ws, x0))
            pts.append((n, dt))
            res[f"weights_win{n}_ms_per_step"] = round(dt / n * 1e3, 3)
        (n1, d1), (n2, d2) = pts
        slope = (d2 - d1) / (n2 - n1)
        res["weights_ms_per_step_slope"] = round(slope * 1e3, 3)

    if "sampler" in stages:
        logits = jax.device_put(
            jax.random.normal(key, (B + 1, V), jnp.float32))
        temps = jnp.full((B + 1,), 0.8, jnp.float32)
        topks = jnp.full((B + 1,), 50, jnp.int32)
        topps = jnp.full((B + 1,), 0.95, jnp.float32)

        def srun(ln, lg):
            def body(c, j):
                salts = jnp.full((B + 1,), j, jnp.int32)
                s = S._sample_topk_core(lg + c[:, None] * 0, temps, topks,
                                        topps, salts)
                return s, s
            return jax.lax.scan(body, jnp.zeros((B + 1,), jnp.int32),
                                jnp.arange(ln))
        pts = []
        for n in (win, 4 * win):
            srun_j = jax.jit(functools.partial(srun, n))
            dt = timed(lambda: srun_j(logits))
            pts.append((n, dt))
            res[f"sampler_win{n}_ms_per_step"] = round(dt / n * 1e3, 3)
        (n1, d1), (n2, d2) = pts
        res["sampler_ms_per_step_slope"] = round(
            (d2 - d1) / (n2 - n1) * 1e3, 3)

    dev = jax.devices()[0]
    res["device"] = str(getattr(dev, "device_kind", dev))
    print(json.dumps(res))


def _make_ws(cfg, key):
    h, f, V = cfg.hidden_size, cfg.ffn_size, cfg.vocab_size
    L = cfg.num_layers
    Ws = {
        "qkv": jnp.zeros((L, h, h + 2 * cfg.num_kv_heads * cfg.head_dim),
                         jnp.bfloat16),
        "proj": jnp.zeros((L, h, h), jnp.bfloat16),
        "gu": jnp.zeros((L, h, 2 * f), jnp.bfloat16),
        "down": jnp.zeros((L, f, h), jnp.bfloat16),
        "head": jnp.zeros((h, V), jnp.bfloat16),
        "emb": jnp.zeros((V, h), jnp.bfloat16),
    }
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(
            jax.random.normal(key, a.shape, jnp.float32).astype(a.dtype)
            * 0.02, jax.devices()[0]), Ws)


if __name__ == "__main__":
    main()

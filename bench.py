"""Flagship benchmark: Llama pretraining step throughput on one TPU chip.

Runs the compiled stacked-Llama training step (the same code path
dryrun_multichip exercises over the hybrid mesh) on a ~0.9B-param Llama
config sized for a single v5e chip, and reports tokens/sec/chip and MFU.

vs_baseline: achieved MFU / 0.45 (the BASELINE.md north-star MFU target for
Llama-2-13B on v5p; same metric, single-chip proxy).

Prints ONE JSON line at the end, AND streams each benchmark's result to
BENCH_partial.jsonl the moment it completes (fsync'd append).  Every
workload — the flagship llama row included — runs under a PER-WORKLOAD
timeout (SIGALRM; ``--timeout-s`` / PT_BENCH_TIMEOUT_S): a workload
that blows its budget is recorded as a ``timed_out`` row and the run
CONTINUES, so the final JSON of record always lands with every finished
row promoted into it (an earlier driver run died with rc 124 and zero parsed
metrics because one slow workload took the whole process down).

``--fast`` runs only the regression-gate rows (llama train, eager
dispatch, serving); ``--full`` (default) runs everything.
``tools/benchgate.py`` consumes the final JSON and fails CI on >5%
drops vs the last good BENCH_r*.json.
"""
import argparse
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

PARTIAL_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_partial.jsonl")


class WorkloadTimeout(Exception):
    """A bench workload exceeded its per-workload budget."""


def run_with_timeout(fn, timeout_s):
    """Run ``fn()`` under a SIGALRM deadline.  Raises WorkloadTimeout
    when the budget expires — the workload's partially-issued device
    work is abandoned (the caller clears caches between rows).  A
    ``timeout_s`` of 0/None runs unguarded."""
    if not timeout_s:
        return fn()

    def _alarm(signum, frame):
        raise WorkloadTimeout(f"workload exceeded {timeout_s}s")

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.setitimer(signal.ITIMER_REAL, float(timeout_s))
    try:
        return fn()
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, old)


def emit_partial(name, payload):
    """Append one benchmark's finished result as a JSONL line, durably:
    write + flush + fsync per line, so a killed process loses at most
    the row in flight — nothing already measured."""
    line = json.dumps({"bench": name, "t": round(time.time(), 3),
                       "result": payload})
    try:
        with open(PARTIAL_PATH, "a") as f:
            f.write(line + "\n")
            f.flush()
            os.fsync(f.fileno())
    except OSError:
        pass


def reset_partial():
    try:
        with open(PARTIAL_PATH, "w") as f:
            f.write("")
    except OSError:
        pass


def peak_flops_per_chip():
    """bf16 peak FLOP/s for the attached chip. A device kind that is not
    listed is an error: a utilization against a guessed peak is not a
    number."""
    kind = jax.devices()[0].device_kind.lower()
    if "v5 lite" in kind or "v5e" in kind:
        return 197e12
    if "v5p" in kind or "v5" in kind:
        return 459e12
    if "v4" in kind:
        return 275e12
    if "v6" in kind or "trillium" in kind:
        return 918e12
    raise ValueError(f"no published bf16 peak for device kind {kind!r}")


def best_of(windows, run_window, sync):
    """min wall-clock over `windows` runs of run_window() (each drained by
    sync() before the clock stops)."""
    dt = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        run_window()
        sync()
        dt = min(dt, time.perf_counter() - t0)
    return dt


def model_flops_per_token(cfg, n_params, seq):
    """Standard MFU accounting (PaLM appendix B): per-token train FLOPs =
    6N (fwd+bwd matmuls) + 12*L*h*s (attention scores+values, fwd+bwd)."""
    return 6 * n_params + \
        12 * cfg.num_hidden_layers * cfg.hidden_size * seq


def bench_resnet50(on_tpu):
    """ResNet-50 DP images/sec (BASELINE row 'ResNet-50 ImageNet'),
    amp O2 bf16 regime (conv/matmul on the MXU in bf16, norms fp32)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    if on_tpu:
        batch, size, steps = 256, 224, 8
    else:
        batch, size, steps = 4, 64, 2
    paddle.seed(0)
    model = resnet50(num_classes=1000)
    if on_tpu:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.Momentum(parameters=model.parameters(),
                                    learning_rate=0.1, momentum=0.9)
    step = TrainStep(model, nn.CrossEntropyLoss(), opt)
    rng = np.random.RandomState(0)
    # stage once: feeding host arrays per step would time the
    # host-to-device copy, not the step
    x = paddle.to_tensor(rng.randn(batch, 3, size, size)
                         .astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype(np.int64))

    def call():
        if on_tpu:
            with paddle.amp.auto_cast(True, level="O1",
                                      dtype="bfloat16"):
                return step(x, y)
        return step(x, y)

    loss = call()
    jax.device_get(loss._value)

    def window():
        nonlocal loss
        for _ in range(steps):
            loss = call()

    dt = best_of(2, window, lambda: jax.device_get(loss._value))
    return {"images_per_sec": round(batch * steps / dt, 1),
            "batch": batch, "image_size": size,
            "loss": float(jax.device_get(loss._value))}


def bench_bert(on_tpu):
    """BERT-base MLM pretrain tokens/sec/chip (BASELINE row
    'ERNIE-3.0 / BERT-base pretrain'), amp O2 bf16 regime (the reference's
    bf16 pretrain recipe: params cast except norms), dropout 0.1 through the
    Pallas flash-attention dropout path."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    if on_tpu:
        cfg = BertConfig(dtype="bfloat16")     # bert-base
        batch, seq, steps = 32, 512, 8
    else:
        from paddle_tpu.models.bert import BERT_PRESETS

        cfg = BERT_PRESETS["debug"]
        batch, seq, steps = 2, 64, 2
    paddle.seed(0)
    model = BertForPretraining(cfg)
    if on_tpu:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")

    class MLMLoss(nn.Layer):
        def __init__(self):
            super().__init__()
            self.ce = nn.CrossEntropyLoss()

        def forward(self, outs, labels):
            mlm_logits = outs[0] if isinstance(outs, (tuple, list)) \
                else outs
            return self.ce(
                mlm_logits.reshape([-1, cfg.vocab_size]),
                labels.reshape([-1]))

    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-4)
    step = TrainStep(model, MLMLoss(), opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    loss = step(ids, labels)
    jax.device_get(loss._value)

    def window():
        nonlocal loss
        for _ in range(steps):
            loss = step(ids, labels)

    dt = best_of(2, window, lambda: jax.device_get(loss._value))
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    tps = batch * seq * steps / dt
    mfu = tps * model_flops_per_token(cfg, n_params, seq) \
        / peak_flops_per_chip()
    return {"tokens_per_sec_per_chip": round(tps, 1),
            "mfu": round(mfu, 4), "batch": batch, "seq": seq,
            "n_params": n_params,
            "loss": float(jax.device_get(loss._value))}


def unet_fwd_flops(cfg, hw, ctx_len=77):
    """Analytic forward FLOPs per image for UNetModel (models/unet.py),
    walking the same down/mid/up structure as forward(). Counts conv and
    matmul FLOPs (2*MACs); norms/activations are omitted (<1%)."""
    def conv(cin, cout, k, h, w):
        return 2 * k * k * cin * cout * h * w

    def attn_block(c, h, w):
        s = h * w
        f = 4 * 2 * s * c * c           # self-attn q/k/v/out projections
        f += 2 * 2 * s * s * c          # self-attn scores + values
        f += 2 * 2 * s * c * c          # cross q + out
        f += 2 * 2 * ctx_len * cfg.context_dim * c   # cross k + v
        f += 2 * 2 * s * ctx_len * c    # cross scores + values
        f += 2 * 2 * s * c * 4 * c      # GELU FFN
        f += 2 * conv(c, c, 1, h, w)    # proj_in + proj_out
        return f

    def res_block(cin, cout, h, w):
        f = conv(cin, cout, 3, h, w) + conv(cout, cout, 3, h, w)
        if cin != cout:
            f += conv(cin, cout, 1, h, w)
        return f

    ch = cfg.base_channels
    total = conv(cfg.in_channels, ch, 3, hw, hw)
    chans = [ch]
    cur, h = ch, hw
    for level, mult in enumerate(cfg.channel_mults):
        oc = ch * mult
        for _ in range(cfg.num_res_blocks):
            total += res_block(cur, oc, h, h)
            if level in cfg.attention_levels:
                total += attn_block(oc, h, h)
            cur = oc
            chans.append(cur)
        if level != len(cfg.channel_mults) - 1:
            total += conv(cur, cur, 3, h // 2, h // 2)  # strided
            chans.append(cur)
            h //= 2
    total += res_block(cur, cur, h, h) * 2 + attn_block(cur, h, h)
    for level, mult in reversed(list(enumerate(cfg.channel_mults))):
        oc = ch * mult
        for _ in range(cfg.num_res_blocks + 1):
            total += res_block(cur + chans.pop(), oc, h, h)
            if level in cfg.attention_levels:
                total += attn_block(oc, h, h)
            cur = oc
        if level != 0:
            h *= 2
            total += conv(cur, cur, 3, h, h)
    total += conv(cur, cfg.out_channels, 3, hw, hw)
    return total


def bench_sd_unet(on_tpu):
    """Stable-Diffusion UNet denoise throughput via the compiler path
    (BASELINE row 'Stable-Diffusion UNet') at FLAGSHIP dims: the full
    sd15 preset (~810M params — SD-1.5's UNet minus its GEGLU gate),
    64x64x4 latents, bf16 compiled denoise step, with analytic-FLOPs MFU
    against the chip's bf16 peak (VERDICT r4 #2)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import to_static
    from paddle_tpu.models.unet import UNET_PRESETS, UNetModel

    if on_tpu:
        cfg = UNET_PRESETS["sd15"]
        batch, hw, steps = 2, 64, 4
    else:
        cfg = UNET_PRESETS["debug"]
        batch, hw, steps = 1, 16, 2
    paddle.seed(0)
    # construct on CPU; jit moves the params to the chip at compile
    with jax.default_device(jax.devices("cpu")[0]):
        model = UNetModel(cfg)
    model.eval()
    if on_tpu:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, 4, hw, hw).astype(np.float32))
    t = paddle.to_tensor(np.full((batch,), 500, np.int64))
    ctx = paddle.to_tensor(rng.randn(batch, 77, cfg.context_dim)
                           .astype(np.float32))

    def fwd(a, b, c):
        if on_tpu:
            with paddle.amp.auto_cast(True, level="O1",
                                      dtype="bfloat16"):
                return model(a, b, c)
        return model(a, b, c)

    step = to_static(fwd)
    out = step(x, t, ctx)
    jax.block_until_ready(out._value)

    def window():
        nonlocal out
        for _ in range(steps):
            out = step(x, t, ctx)

    dt = best_of(2, window, lambda: jax.device_get(out._value))
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops = unet_fwd_flops(cfg, hw)
    mfu = flops * batch * steps / dt / peak_flops_per_chip()
    return {"denoise_steps_per_sec": round(steps / dt, 2),
            "latents_per_sec": round(batch * steps / dt, 2),
            "batch": batch, "latent_hw": hw, "n_params": n_params,
            "fwd_tflops_per_image": round(flops / 1e12, 3),
            "mfu": round(mfu, 4)}


def bench_llama13b_block(on_tpu):
    """One transformer block at Llama-2-13B dimensions (hidden 5120,
    40 heads, seq 4096, bf16) — the 13B-class scale evidence VERDICT r2
    #5 asks for: per-block MFU on one chip plus validation of the
    auto-tuner memory model (predicted vs XLA-measured bytes) so the
    v5p-128 13B projection is grounded."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from validate_memory_model import block_step_memory, build_block_step

    if on_tpu:
        hidden, inter, heads, seq, batch = 5120, 13824, 40, 4096, 2
    else:
        hidden, inter, heads, seq, batch = 128, 344, 4, 256, 1
    # no-remat is the faster single-block regime (flash attention keeps
    # temps small; remat only pays off across a deep stack)
    step, blocks, opt, x, n_blk = build_block_step(
        hidden, inter, heads, seq, batch, layers=1, remat=False)
    jitted = jax.jit(step, donate_argnums=(0, 1))
    blocks, opt, loss = jitted(blocks, opt, x)
    jax.device_get(loss)
    steps = 10 if on_tpu else 2

    def window():
        nonlocal blocks, opt, loss
        for _ in range(steps):
            blocks, opt, loss = jitted(blocks, opt, x)

    dt = best_of(2, window, lambda: jax.device_get(loss))
    tok_s = batch * seq * steps / dt
    mfu = tok_s * (6 * n_blk + 12 * hidden * seq) / peak_flops_per_chip()

    # memory-model validation on the remat train regime (13B runs remat)
    pred, meas, _ = block_step_memory(hidden, inter, heads, seq, batch,
                                      layers=1, remat=True)
    return {"tokens_per_sec": round(tok_s, 1),
            "per_block_mfu": round(mfu, 4),
            "hidden": hidden, "heads": heads, "seq": seq, "batch": batch,
            "block_params": n_blk,
            "mem_model_predicted_gb": round(pred / 1e9, 3),
            "mem_model_measured_gb": round(meas / 1e9, 3),
            "mem_model_ratio": round(pred / meas, 3)}


def bench_serving(on_tpu):
    """Paged-KV continuous-batching serving throughput at flagship dims
    (VERDICT r3 #1): the ~0.9B llama GQA config decoding through the
    ServingEngine on one chip — prefill ingest rate plus decode
    tokens/s/chip at batch 4 and 8 with temperature/top-k/top-p sampling.
    Decode windows run through `decode_run` (device-fed multi-step
    decode, one host sync per window)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              SamplingParams,
                                              ServingEngine)

    if on_tpu:
        # decode at small batch is weight-read bound (M=8 GEMMs stream
        # ~120 GB/s on v5e), so tokens/s scales close to linearly in
        # the decode batch — measure 4/8/16, each with a tight engine
        # (the model's forward derives batch dims from inputs, so one
        # weight set serves every engine). Decode windows are 96 steps.
        prompt_len, max_new, win = 128, 300, 96
        batches = (4, 8, 16)
        quants = (None, "int8")

        def mk_cfg(B, quant=None):
            return PagedServingConfig.llama_1b(
                max_batch=B, num_blocks=B * 14 + 16,
                max_blocks_per_seq=14, cache_quant=quant)
    else:
        def mk_cfg(B, quant=None):
            return PagedServingConfig(vocab_size=128, hidden_size=32,
                                      num_layers=2, num_heads=4,
                                      num_kv_heads=2, ffn_size=64,
                                      block_size=8, num_blocks=32,
                                      max_batch=B, max_blocks_per_seq=4,
                                      token_budget=32, cache_quant=quant)
        prompt_len, max_new, win = 8, 12, 4
        batches = (2,)
        quants = (None,)
    paddle.seed(0)
    cfg = mk_cfg(batches[0])
    # construct on CPU; from_model stages the cast weights into HBM once
    with jax.default_device(jax.devices("cpu")[0]):
        model = PagedCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    sp = SamplingParams(temperature=0.8, top_k=50, top_p=0.95)
    rows = {}
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    for B in batches:
        for quant in (quants if B == max(batches) else (None,)):
            cfg = mk_cfg(B, quant)
            engine = ServingEngine.from_model(model, cfg, seed=0)
            for _ in range(B):
                engine.add_request(
                    list(rng.randint(1, cfg.vocab_size, prompt_len)),
                    max_new_tokens=max_new, sampling=sp)
            engine.step()                  # compile (prefill-shaped step)
            while any(r.length - r.cached > 1 for r in engine.pending()):
                engine.step()              # finish wave-1 prefill (warm)
            engine.decode_run(win)         # warm the win-sized window fn

            # wave 2 on the warmed engine: per-request TTFT percentiles
            eng2 = ServingEngine.from_model(model, cfg, seed=1)
            t_submit = time.perf_counter()
            rids = [eng2.add_request(
                list(rng.randint(1, cfg.vocab_size, prompt_len)),
                max_new_tokens=max_new, sampling=sp) for _ in range(B)]
            ttft = {}
            steps = 0
            while any(r.length - r.cached > 1 for r in eng2.pending()):
                produced = eng2.step()
                steps += 1
                now = time.perf_counter()
                for rid, _ in produced:
                    ttft.setdefault(rid, now - t_submit)
            prefill_dt = time.perf_counter() - t_submit
            ttft_v = sorted(ttft.values())

            # decode TPOT spread over full windows (a tail window
            # shrunken by the remaining-token budget would skew /win).
            # Only a handful of windows fit the max_new budget, so the
            # honest fields are min/max per-step time, not percentiles
            # (two samples gave a meaningless "p95").
            win_ms = []
            for _ in range(2):
                t0 = time.perf_counter()
                out = engine.decode_run(win)
                if len(out) < win * B:
                    break
                win_ms.append((time.perf_counter() - t0) / win * 1e3)
            win_ms.sort()
            dt = win_ms[0] * win / 1e3 if win_ms else float("inf")
            key = f"decode_batch{B}" + ("_int8" if quant else "")
            rows[key] = {
                "decode_tokens_per_sec": round(win * B / dt, 1),
                "step_ms": round(win_ms[0], 2) if win_ms else None,
                "tpot_ms_min": round(win_ms[0], 2) if win_ms else None,
                "tpot_ms_max": round(win_ms[-1], 2) if win_ms else None,
                "ttft_s_p50": round(float(np.percentile(ttft_v, 50)), 3)
                if ttft_v else None,
                "ttft_s_p95": round(float(np.percentile(ttft_v, 95)), 3)
                if ttft_v else None,
                "mixed_prefill_steps": steps,
                "prefill_dt_s": round(prefill_dt, 3),
                "prefill_tokens_per_sec": round(
                    B * prompt_len / prefill_dt, 1),
                "cache_gb": round(
                    2 * np.prod([cfg.num_layers, cfg.num_blocks,
                                 cfg.num_kv_heads, cfg.block_size,
                                 cfg.head_dim])
                    * (1 if quant else 2) / 1e9, 3),
                "generated_ok": all(len(r.generated) > 0
                                    for r in engine._requests.values()),
            }
    rows.update({"n_params": n_params, "hidden": cfg.hidden_size,
                 "layers": cfg.num_layers,
                 "heads": f"{cfg.num_heads}q/{cfg.num_kv_heads}kv",
                 "dtype": cfg.dtype, "prompt_len": prompt_len,
                 "decode_window": win,
                 "sampling": "temp0.8/top_k50/top_p0.95"})
    return rows


def bench_fleet_serving(on_tpu):
    """Fleet serving gate row (ISSUE 7): (a) a shared-prefix workload —
    N requests behind one common system prompt — served WITH and WITHOUT
    the prefix cache (requests/s, mean TTFT, hit rate: the benchgate
    fleet signals), and (b) the int8 double-buffered weight-streaming
    decode step vs the bf16 non-prefetched baseline (honest min/max
    spread — decode here is weight-streaming-bound, PR 2).  Tail
    latencies (ttft p50/p95/p99, tpot percentiles) come from the
    per-wave child-registry t-digests (PR 10) — honest quantiles, not
    means — and the wave's request spans land in a chrome-trace
    artifact next to the bench results."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              ServingEngine)
    from paddle_tpu.inference.weight_stream import measure_stream_win
    from paddle_tpu.profiler import tracing as _tracing

    _tracing.clear_ring()

    if on_tpu:
        n_req, prefix_len, unique_len, max_new = 16, 512, 32, 32
        stream_batch, stream_win = 16, 48

        def mk_cfg(**over):
            base = dict(max_batch=8, num_blocks=8 * 20 + 64,
                        max_blocks_per_seq=20)
            base.update(over)
            return PagedServingConfig.llama_1b(**base)
    else:
        n_req, prefix_len, unique_len, max_new = 16, 96, 8, 4
        stream_batch, stream_win = 4, 4

        def mk_cfg(**over):
            base = dict(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=4, num_kv_heads=2, ffn_size=64,
                        block_size=8, num_blocks=96, max_batch=4,
                        max_blocks_per_seq=16, token_budget=32)
            base.update(over)
            return PagedServingConfig(**base)
    paddle.seed(0)
    cfg = mk_cfg()
    with jax.default_device(jax.devices("cpu")[0]):
        model = PagedCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    prefix = list(rng.randint(1, cfg.vocab_size, prefix_len))
    prompts = [prefix + list(rng.randint(1, cfg.vocab_size, unique_len))
               for _ in range(n_req)]

    def serve_wave(prefix_cache, seed):
        model._serving_shared = None
        eng = ServingEngine.from_model(model, mk_cfg(
            prefix_cache=prefix_cache), seed=seed)
        # warm the executables off the clock; on the cache engine this
        # also seeds the shared system prompt — the fleet steady state
        # (so all n_req timed requests are prefix hits)
        eng.add_request(prompts[0], max_new_tokens=1)
        eng.run_to_completion()
        eng._requests.clear()
        from paddle_tpu.profiler import metrics as _m

        # per-wave child registry AFTER warm-up: the digest sees only
        # the timed requests, never the compile-heavy warm request
        ns = f"wave-{'pc' if prefix_cache else 'nc'}"
        eng.set_metrics_namespace(ns)
        reused0 = _m.counter("serving/prefix_pages_reused").value
        t0 = time.perf_counter()
        rids = [eng.add_request(p, max_new_tokens=max_new)
                for p in prompts]
        ttft = {}
        while eng.pending():
            produced = eng.step()
            now = time.perf_counter()
            for rid, _ in produced:
                ttft.setdefault(rid, now - t0)
        dt = time.perf_counter() - t0
        assert all(len(eng._requests[r].generated) == max_new
                   for r in rids)
        hit_rate = eng._prefix_cache.hit_rate() \
            if eng._prefix_cache is not None else 0.0
        reused = _m.counter("serving/prefix_pages_reused").value - reused0
        ttft_h = _m.child(ns).histogram("serving/ttft_ms")
        qs = {q: ttft_h.quantile(q) for q in (0.5, 0.95, 0.99)}
        return (n_req / dt, float(np.mean(list(ttft.values()))),
                hit_rate, reused, qs)

    rps_nc, ttft_nc, _, _, _ = serve_wave(False, seed=1)
    rps_pc, ttft_pc, hit_rate, pages_reused, ttft_qs = \
        serve_wave(True, seed=1)

    # -- int8 double-buffered weight streaming micro-bench ---------------
    def decode_setup(weight_stream):
        model._serving_shared = None
        eng = ServingEngine.from_model(model, mk_cfg(
            max_batch=stream_batch), seed=2,
            weight_stream=weight_stream)
        rngd = np.random.RandomState(3)
        for _ in range(stream_batch):
            eng.add_request(
                list(rngd.randint(1, cfg.vocab_size, unique_len)),
                max_new_tokens=8 * stream_win)
        while any(r.length - r.cached > 1 for r in eng.pending()):
            eng.step()
        eng.decode_run(stream_win)          # warm the window executable
        # child registry AFTER the warm window: the tpot digest holds
        # only requests that finish in the timed windows (`serving/tpot_ms`
        # is observed once a request finishes, on every path; a window
        # that finishes none observes nothing and `tpot_qs` reads empty)
        eng.set_metrics_namespace(f"stream-{weight_stream or 'bf16'}")
        return eng

    def time_windows(eng, n=3):
        ms = []
        for _ in range(n):
            t0 = time.perf_counter()
            out = eng.decode_run(stream_win)
            if len(out) < stream_win * stream_batch:
                break
            ms.append((time.perf_counter() - t0) / stream_win * 1e3)
        return sorted(ms)

    eng_base = decode_setup(None)
    eng_stream = decode_setup("int8")
    base_ms = time_windows(eng_base)
    stream_ms = time_windows(eng_stream)
    win_ms, _, _ = measure_stream_win(
        lambda: eng_stream.decode_run(1) or eng_stream._kc,
        lambda: eng_base.decode_run(1) or eng_base._kc)

    from paddle_tpu.profiler import metrics as _m

    def tpot_qs(ns):
        h = _m.child(ns).histogram("serving/tpot_ms")
        return {f"tpot_ms_p{int(q * 100)}": round(h.quantile(q), 3)
                for q in (0.5, 0.95, 0.99) if h.quantile(q) is not None}

    trace_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_fleet_trace.json")
    _tracing.export_chrome(trace_path)

    def ms_to_s(v):
        return round(v / 1e3, 4) if v is not None else None

    return {
        "fleet": {
            "n_requests": n_req, "prefix_len": prefix_len,
            "unique_len": unique_len, "max_new": max_new,
            "requests_per_sec": round(rps_pc, 2),
            "requests_per_sec_nocache": round(rps_nc, 2),
            "speedup_vs_nocache": round(rps_pc / rps_nc, 3),
            "ttft_mean_s": round(ttft_pc, 4),
            "ttft_mean_s_nocache": round(ttft_nc, 4),
            # digest tail latency (engine-side submit->first-token) —
            # benchgate gates ttft_p95_s with the standard threshold
            "ttft_p50_s": ms_to_s(ttft_qs.get(0.5)),
            "ttft_p95_s": ms_to_s(ttft_qs.get(0.95)),
            "ttft_p99_s": ms_to_s(ttft_qs.get(0.99)),
            "prefix_hit_rate": round(hit_rate, 4),
            "prefix_pages_reused": pages_reused,
            "trace_artifact": os.path.basename(trace_path),
        },
        "weight_stream": {
            "decode_batch": stream_batch, "window": stream_win,
            "step_ms_bf16_min": round(base_ms[0], 3) if base_ms else None,
            "step_ms_bf16_max": round(base_ms[-1], 3) if base_ms else None,
            "step_ms_int8_stream_min":
                round(stream_ms[0], 3) if stream_ms else None,
            "step_ms_int8_stream_max":
                round(stream_ms[-1], 3) if stream_ms else None,
            "stream_speedup": round(base_ms[0] / stream_ms[0], 3)
                if base_ms and stream_ms else None,
            "prefetch_win_ms": round(win_ms, 3),
            "bf16": tpot_qs("stream-bf16"),
            "int8_stream": tpot_qs("stream-int8"),
        },
    }


def bench_fleet_recovery(on_tpu):
    """Fleet recovery gate row (ISSUE 9): two replicas behind the
    router + fleet supervisor; PT_FAULT_PLAN kills one mid-decode.
    Gate signals: every admitted request completes (drain migrates
    decode-tip requests to the peer, requeues the rest) and how many
    seconds the drain + backoff restart takes.  Bitwise parity vs an
    uninterrupted reference run is recorded alongside.

    PR 10 observability riders: the chaos run's spans export as a
    merged chrome trace (the drained request's pre- and post-migration
    spans share one trace id — asserted in ``trace_connected``), the
    killed engine's flight recorder lands next to the bench results,
    and an in-process FleetAggregator reports per-replica digest p95
    TTFT from the replicas' child registries."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.resilience import faults
    from paddle_tpu.inference.fleet_supervisor import (
        FleetSupervisor, FleetSupervisorConfig)
    from paddle_tpu.inference.router import Replica, ReplicaRouter
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              SamplingParams,
                                              ServingEngine)
    from paddle_tpu.profiler import aggregate as _aggregate
    from paddle_tpu.profiler import metrics as _pmetrics
    from paddle_tpu.profiler import tracing as _tracing

    bench_dir = os.path.dirname(os.path.abspath(__file__))
    flight_dir = os.path.join(bench_dir, "BENCH_flight")
    _tracing.set_flight_dir(flight_dir)

    n_req, prompt_len, max_new = 8, 12, 6
    cfg = PagedServingConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, ffn_size=64, block_size=8, num_blocks=64,
        max_batch=4, max_blocks_per_seq=6, token_budget=32)
    paddle.seed(0)
    with jax.default_device(jax.devices("cpu")[0]):
        model = PagedCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(1, cfg.vocab_size, prompt_len))
               for _ in range(n_req)]
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)

    def factory(idx):
        return ServingEngine.from_model(model, cfg, seed=10 + idx)

    def build():
        engines = [factory(i) for i in range(2)]
        for i, e in enumerate(engines):
            e.fault_rank = i
        router = ReplicaRouter(
            [Replica(e, name=f"r{i}", restore_after=2)
             for i, e in enumerate(engines)])
        sup = FleetSupervisor(router, engine_factory=factory,
                              cfg=FleetSupervisorConfig(
                                  backoff_base_s=0.005))
        return router, sup

    def drive(router):
        hs = [router.submit(p, max_new_tokens=max_new, sampling=sp)
              for p in prompts]
        out = router.run_to_completion()
        return {h: out[h] for h in hs}

    faults.disarm()
    router, _ = build()
    ref = drive(router)                      # warm + reference streams

    faults.arm("kill@decode#2:rank=1")
    router, sup = build()
    _tracing.clear_ring()                    # chaos-run spans only
    flight_before = set(os.listdir(flight_dir)) \
        if os.path.isdir(flight_dir) else set()
    recovery = {}
    on_failure = sup.on_failure

    def timed_failure(idx):
        t0 = time.perf_counter()
        on_failure(idx)
        recovery["s"] = recovery.get("s", 0.0) \
            + (time.perf_counter() - t0)
    router.failure_hook = timed_failure
    t0 = time.perf_counter()
    out = drive(router)
    total_s = time.perf_counter() - t0
    faults.disarm()

    completed = sum(1 for toks in out.values() if len(toks) == max_new)

    # merged chrome trace + connectivity check: some trace id must hold
    # BOTH a hand-off-out span (migrate/requeue, recorded on the dying
    # engine) and its continuation on the surviving peer
    trace_path = os.path.join(bench_dir, "BENCH_recovery_trace.json")
    spans = _tracing.ring_spans()
    _tracing.export_chrome(trace_path, spans=spans)
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], set()).add(s["name"])
    trace_connected = any(
        ("serving::migrate" in names and "serving::migrate_in" in names)
        or "serving::requeue" in names for names in by_trace.values())

    flight_files = sorted(
        set(os.listdir(flight_dir)) - flight_before) \
        if os.path.isdir(flight_dir) else []
    _tracing.set_flight_dir(None)

    # fleet snapshot from the replicas' child registries: per-replica
    # digest p95 TTFT, the number a FleetGateway would route on
    agg = _aggregate.FleetAggregator()
    for rep in router.replicas:
        ns = getattr(rep.engine, "metrics_namespace", None)
        if ns is None:
            continue
        snap = _pmetrics.child(ns).snapshot()
        snap["host_id"] = rep.host_id or "local"
        snap["replica"] = rep.name
        agg.ingest(snap)
    ttft_p95 = {
        f"{host}/{rep}": round(v, 3)
        for (host, rep) in agg.keys()
        for v in [agg.percentile("serving/ttft_ms", 0.95,
                                 host_id=host, replica=rep)]
        if v is not None}

    return {"fleet_recovery": {
        "n_requests": n_req, "max_new": max_new,
        "requests_completed": completed,
        "recovery_s": round(recovery.get("s", 0.0), 4),
        "total_s": round(total_s, 4),
        "replica_restarts": sum(sup.restarts),
        "drained": len(sup.drained_handles),
        "bitwise_match": out == ref,
        "trace_artifact": os.path.basename(trace_path),
        "trace_connected": trace_connected,
        "flight_dumps": flight_files,
        "ttft_p95_ms_per_replica": ttft_p95,
    }}


def bench_host_recovery(on_tpu):
    """Host-loss recovery gate row (ISSUE 10): four replicas on two
    simulated hosts (h0,h0,h1,h1) behind the router + fleet supervisor;
    PT_FAULT_PLAN fells host h1 mid-decode, killing BOTH its replicas
    (the injector's sticky felled-host semantics).  Gate signals:
    every admitted request completes — drains land off-host first, on
    the surviving h0 replicas — and how many seconds the drain +
    backoff restarts take.  Restarted engines come back on h0 (the
    felled host stays dead), and bitwise parity vs an uninterrupted
    reference run is recorded alongside."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.resilience import faults
    from paddle_tpu.inference.fleet_supervisor import (
        FleetSupervisor, FleetSupervisorConfig)
    from paddle_tpu.inference.router import Replica, ReplicaRouter
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              SamplingParams,
                                              ServingEngine)
    from paddle_tpu.profiler import metrics as _metrics

    n_req, prompt_len, max_new = 8, 12, 6
    hosts = ("h0", "h0", "h1", "h1")
    cfg = PagedServingConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, ffn_size=64, block_size=8, num_blocks=64,
        max_batch=4, max_blocks_per_seq=6, token_budget=32)
    paddle.seed(0)
    with jax.default_device(jax.devices("cpu")[0]):
        model = PagedCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(1, cfg.vocab_size, prompt_len))
               for _ in range(n_req)]
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)

    def factory(idx):
        e = ServingEngine.from_model(model, cfg, seed=20 + idx)
        e.host_id = "h0"        # restarts land on the surviving host
        return e

    def build():
        engines = []
        for i in range(4):
            e = ServingEngine.from_model(model, cfg, seed=20 + i)
            e.fault_rank = i
            e.host_id = hosts[i]
            engines.append(e)
        router = ReplicaRouter(
            [Replica(e, name=f"r{i}", restore_after=2)
             for i, e in enumerate(engines)])
        sup = FleetSupervisor(router, engine_factory=factory,
                              cfg=FleetSupervisorConfig(
                                  backoff_base_s=0.005))
        return router, sup

    def drive(router):
        hs = [router.submit(p, max_new_tokens=max_new, sampling=sp)
              for p in prompts]
        out = router.run_to_completion()
        return {h: out[h] for h in hs}

    faults.disarm()
    router, _ = build()
    ref = drive(router)                      # warm + reference streams

    cross0 = _metrics.counter("serving/cross_host_drains").value
    faults.arm("kill@host#2:host=h1")
    router, sup = build()
    recovery = {}
    on_failure = sup.on_failure

    def timed_failure(idx):
        t0 = time.perf_counter()
        on_failure(idx)
        recovery["s"] = recovery.get("s", 0.0) \
            + (time.perf_counter() - t0)
    router.failure_hook = timed_failure
    t0 = time.perf_counter()
    out = drive(router)
    total_s = time.perf_counter() - t0
    faults.disarm()

    completed = sum(1 for toks in out.values() if len(toks) == max_new)
    return {"host_recovery": {
        "n_requests": n_req, "max_new": max_new,
        "requests_completed": completed,
        "recovery_s": round(recovery.get("s", 0.0), 4),
        "total_s": round(total_s, 4),
        "replica_restarts": sum(sup.restarts),
        "drained": len(sup.drained_handles),
        "cross_host_drains":
            _metrics.counter("serving/cross_host_drains").value - cross0,
        "bitwise_match": out == ref,
    }}


def bench_fleet_subprocess(on_tpu):
    """Process-isolated fleet gate row (ISSUE 20): two SUBPROCESS
    replicas (inference/remote_replica.py) behind the router + fleet
    supervisor; ``sigkill@replica`` SIGKILLs one worker PROCESS
    mid-decode.  Unlike ``fleet_recovery`` the failure is a real pod
    kill: the parent infers death from missed heartbeats, the drain's
    dead-process path requeues the victim's streams to the surviving
    worker, and a fresh process is respawned through the factory.
    Gate signals: every admitted request completes and every finished
    stream stays token-bitwise-identical to the uninterrupted
    in-process reference (zero-slack both); drain and respawn wall
    times are recorded alongside (not zero-slack — respawn pays a
    full interpreter + jax start)."""
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.distributed.resilience import faults
    from paddle_tpu.inference.fleet_supervisor import (
        FleetSupervisor, FleetSupervisorConfig)
    from paddle_tpu.inference.remote_replica import (
        SubprocessReplicaFactory, sweep_orphans)
    from paddle_tpu.inference.router import ReplicaRouter
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              SamplingParams,
                                              ServingEngine)

    n_req, prompt_len, max_new = 6, 12, 6
    cfg_kwargs = dict(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, ffn_size=64, block_size=8, num_blocks=64,
        max_batch=4, max_blocks_per_seq=6, token_budget=32)
    model_seed = 0
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(1, cfg_kwargs["vocab_size"], prompt_len))
               for _ in range(n_req)]
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)

    def pin(engine, rid, key):
        r = engine._requests[rid]
        r.salt_rid, r.salt_seed = int(key), 0

    # uninterrupted in-process reference: same model seed the workers
    # rebuild from, streams keyed by their pinned salt identity
    cfg = PagedServingConfig(**cfg_kwargs)
    paddle.seed(model_seed)
    with jax.default_device(jax.devices("cpu")[0]):
        model = PagedCausalLM(cfg)
    model.eval()
    ref_eng = ServingEngine.from_model(model, cfg, seed=99)
    ref = {}
    for i, p in enumerate(prompts):
        rid = ref_eng.add_request(list(p), max_new_tokens=max_new,
                                  sampling=sp)
        pin(ref_eng, rid, 3000 + i)
        ref[3000 + i] = rid
    while ref_eng.pending():
        ref_eng.step()
    ref = {k: list(ref_eng._requests[rid].generated)
           for k, rid in ref.items()}

    factory = SubprocessReplicaFactory(
        cfg_kwargs, model_seed=model_seed, seed_base=10,
        pid_dir=tempfile.mkdtemp(prefix="bench_subproc_"),
        hb_interval_s=0.25, hb_miss_n=40, ack_timeout=5.0,
        rpc_timeout=300.0, spawn_timeout=300.0)
    row = {}
    try:
        router = ReplicaRouter([factory.build(0), factory.build(1)])
        sup = FleetSupervisor(router, factory.make_engine_factory(),
                              cfg=FleetSupervisorConfig(restart=False))
        recovery = {}
        on_failure = sup.on_failure

        def timed_failure(idx):
            t0 = time.perf_counter()
            on_failure(idx)
            recovery["s"] = recovery.get("s", 0.0) \
                + (time.perf_counter() - t0)
        router.failure_hook = timed_failure

        # warm round: compile both children's decode graphs so the
        # chaos round measures the fleet, not jax tracing
        warm = [router.submit(prompts[i], max_new_tokens=max_new,
                              sampling=sp, prefer=i) for i in range(2)]
        router.run_to_completion(max_steps=100000)

        victim = router.replicas[1].engine
        faults.arm(f"sigkill@replica#3:rank={victim.child_rank}")
        hs = {}
        for i, p in enumerate(prompts):
            h = router.submit(p, max_new_tokens=max_new, sampling=sp)
            idx, rid = router._handles[h]
            pin(router.replicas[idx].engine, rid, 3000 + i)
            hs[h] = 3000 + i
        t0 = time.perf_counter()
        deadline = t0 + 240.0
        while router._live_pending() \
                and time.perf_counter() < deadline:
            router.step_all()
            time.sleep(0.005)
        total_s = time.perf_counter() - t0
        faults.disarm()
        out = router.results()

        completed = sum(1 for h in hs if len(out[h]) == max_new)
        bitwise = all(out[h] == ref[k] for h, k in hs.items())

        # respawn through the factory: a fresh process (fresh
        # transport rank) joining the fleet, timed separately — it
        # pays full interpreter + jax + compile start
        t1 = time.perf_counter()
        spawned = factory.build(2)
        router.add_replica(spawned)
        respawn_s = time.perf_counter() - t1
        row = {
            "n_requests": n_req, "max_new": max_new,
            "requests_completed": completed,
            "bitwise_match": bool(bitwise),
            "recovery_s": round(recovery.get("s", 0.0), 4),
            "detect_s": round(victim.beat_budget(), 4),
            "total_s": round(total_s, 4),
            "respawn_s": round(respawn_s, 4),
            "victim_exit_class":
                (victim.death or {}).get("exit_class"),
            "respawned_placeable": bool(spawned.placeable()),
        }
    finally:
        pid_dir = factory.pid_dir
        factory.close()
        row["orphans_after_close"] = len(sweep_orphans(pid_dir))
    return {"fleet_subprocess": row}


def bench_gateway_storm(on_tpu):
    """Gateway overload gate row (ISSUE 12): two replicas behind the
    FleetGateway; the ``overload@admit`` chaos pattern turns every
    arriving request into 4 (three synthetic best-effort clones under
    the ``_storm`` tenant).  Gate signals: every interactive request
    completes with zero deadline misses once the brownout ladder
    engages, goodput holds, and every completed real stream stays
    token-bitwise-identical to the unloaded reference run (clamped
    batch streams must be exact PREFIXES of their reference — the
    ladder may shorten a stream, never alter it)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.resilience import faults
    from paddle_tpu.inference.gateway import (BrownoutConfig,
                                              FleetGateway,
                                              GatewayConfig,
                                              SLOClassConfig,
                                              TenantConfig,
                                              BROWNOUT_LEVELS)
    from paddle_tpu.inference.router import Replica, ReplicaRouter
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              SamplingParams,
                                              ServingEngine)
    from paddle_tpu.profiler import metrics as _pmetrics
    from paddle_tpu.profiler import timeline as _ptimeline
    from paddle_tpu.profiler import tracing as _ptracing
    from paddle_tpu.profiler.headroom import ScaleAdvisor
    from paddle_tpu.profiler.slo import SLOObjective, SLOTracker

    n_int, n_batch, prompt_len, max_new = 6, 4, 12, 6
    cfg = PagedServingConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, ffn_size=64, block_size=8, num_blocks=64,
        max_batch=4, max_blocks_per_seq=6, token_budget=32,
        max_queue=6, prefix_cache=True)
    paddle.seed(0)
    with jax.default_device(jax.devices("cpu")[0]):
        model = PagedCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(13)
    int_prompts = [list(rng.randint(1, cfg.vocab_size, prompt_len))
                   for _ in range(n_int)]
    batch_prompts = [list(rng.randint(1, cfg.vocab_size, prompt_len))
                     for _ in range(n_batch)]
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)

    def gateway_cfg():
        return GatewayConfig(
            classes={
                "interactive": SLOClassConfig(deadline_s=5.0,
                                              priority=0,
                                              protected=True),
                "batch": SLOClassConfig(deadline_s=30.0, priority=1,
                                        deferrable=True),
                "best_effort": SLOClassConfig(priority=2,
                                              sheddable=True),
            },
            tenants={"alpha": TenantConfig(rate=500.0, burst=100.0,
                                           weight=2.0),
                     "beta": TenantConfig(rate=500.0, burst=100.0,
                                          weight=1.0)},
            brownout=BrownoutConfig(enter_load=1.2, exit_load=0.6,
                                    hysteresis=2, clamp_max_new=4,
                                    retry_after_s=0.25),
            retry_cap=20.0, retry_deposit=0.2, retry_floor=4.0)

    def build():
        engines = []
        for i in range(2):
            e = ServingEngine.from_model(model, cfg, seed=30 + i)
            e.fault_rank = i
            engines.append(e)
        router = ReplicaRouter(
            [Replica(e, name=f"r{i}") for i, e in enumerate(engines)])
        return FleetGateway(router, gateway_cfg())

    def drive(gw):
        """Submit the REAL mixed-tenant request set (stable stream
        keys — the bitwise identity) and run the fleet dry."""
        t_int, t_batch = [], []
        for i, p in enumerate(int_prompts):
            t_int.append(gw.submit(p, max_new_tokens=max_new,
                                   sampling=sp, tenant="alpha",
                                   slo="interactive",
                                   stream_key=1000 + i))
        for i, p in enumerate(batch_prompts):
            t_batch.append(gw.submit(p, max_new_tokens=max_new,
                                     sampling=sp, tenant="beta",
                                     slo="batch", stream_key=2000 + i))
        out = gw.run_to_completion(max_steps=4000)
        return t_int, t_batch, out

    faults.disarm()
    gw = build()
    t_int, t_batch, out = drive(gw)          # warm + unloaded reference
    ref = {gw.ticket_info(t)["stream_key"]: out.get(t, [])
           for t in t_int + t_batch}

    storm0 = _pmetrics.counter("gateway/storm_injected").value
    shed0 = _pmetrics.counter("gateway/shed").value
    defer0 = _pmetrics.counter("gateway/deferrals").value
    requeue0 = _pmetrics.counter("serving/requeues").value
    exhausted0 = _pmetrics.counter("serving/requeue_exhausted").value

    # -- SLO engine (ISSUE 16): timeline + burn alerts + headroom over
    # the storm.  Everything runs on a synthetic step-counter clock
    # (one tick per gateway step) so window math is deterministic on
    # any host — wall-clock never enters the alert logic.
    import tempfile
    step_count = [0]
    spill_dir = tempfile.mkdtemp(prefix="pt_timeline_")
    flight_dir = tempfile.mkdtemp(prefix="pt_flight_")
    tl = _ptimeline.Timeline(clock=lambda: float(step_count[0]),
                             spill_dir=spill_dir)
    tracker = SLOTracker(
        class_objectives={"interactive": SLOObjective(target=0.999),
                          "batch": SLOObjective(target=0.99),
                          "best_effort": SLOObjective(target=0.99)},
        clock=lambda: float(step_count[0]),
        fast_window_s=40.0, slow_window_s=4000.0,
        burn_threshold=10.0, clear_after=3)
    advisor = ScaleAdvisor(tl, tracker, window_s=40.0, min_windows=3)
    prev_flight_dir = _ptracing.flight._dir
    _ptimeline.install(tl)
    tl.attach_flight(n=400)
    _ptracing.set_flight_dir(flight_dir)

    gw = build()
    tracker.attach(gw)

    def tick(every: int = 5):
        step_count[0] += 1
        if step_count[0] % every == 0:
            tl.sample()
            tracker.evaluate()

    advice_during = None
    dump_path = None
    try:
        for _ in range(15):                  # pre-storm calm windows
            gw.step()
            tick()
        prestorm_seq = tl.windows()[-1]["seq"]

        faults.arm("overload@admit%1.0:x=4")
        t0 = time.perf_counter()
        for i, p in enumerate(int_prompts):
            t_int[i] = gw.submit(p, max_new_tokens=max_new,
                                 sampling=sp, tenant="alpha",
                                 slo="interactive", stream_key=1000 + i)
        for i, p in enumerate(batch_prompts):
            t_batch[i] = gw.submit(p, max_new_tokens=max_new,
                                   sampling=sp, tenant="beta",
                                   slo="batch", stream_key=2000 + i)
        for _ in range(4000):
            gw.step()
            tick()
            if advice_during is None and gw.brownout.level >= 1 \
                    and len(tl.windows()) >= 2:
                advice_during = advisor.recommend()
            if not gw.queued() and not gw.router._live_pending():
                break
        out = gw.results()
        total_s = time.perf_counter() - t0
        faults.disarm()

        # recovery: idle ticks age the storm out of the fast window so
        # the burn alert clears (hysteresis: 3 calm evals) and the
        # brownout ladder unwinds out of the advisor's horizon; the
        # post-recovery advisory is taken 20 virtual steps after the
        # clear — late enough that the ladder's last engaged window
        # left the horizon, soon enough that the cleared-alert edge is
        # still inside it (recent judgment vetoes a scale_down)
        cleared_at = None
        advice_after = None
        for _ in range(120):
            gw.step()
            tick()
            if cleared_at is None and tracker.alerts \
                    and not tracker.active_alerts():
                cleared_at = step_count[0]
            if advice_after is None and cleared_at is not None \
                    and step_count[0] >= cleared_at + 20:
                advice_after = advisor.recommend()
        if advice_after is None:
            advice_after = advisor.recommend()
        dump_path = _ptracing.flight_dump("gateway_storm_postmortem",
                                          storm_factor=4)
    finally:
        faults.disarm()
        _ptimeline.uninstall(tl)
        _ptracing.flight.detach("timeline")
        _ptracing.set_flight_dir(prev_flight_dir)

    slo_report = tracker.report()
    flight_prestorm = False
    if dump_path:
        with open(dump_path) as f:
            flight_windows = json.load(f).get("timeline", [])
        flight_prestorm = any(w.get("seq", 1 << 30) <= prestorm_seq
                              for w in flight_windows)
    alerts_raised = len(tracker.alerts)
    alerts_cleared = sum(1 for a in tracker.alerts if not a.active)

    # bitwise discipline: under 4x overload every completed REAL
    # stream must be a bitwise prefix of its unloaded reference, and
    # protected interactive streams must be complete AND exact
    bitwise = True
    for t in t_int + t_batch:
        toks = out.get(t)
        if not toks:
            continue
        r = ref[gw.ticket_info(t)["stream_key"]]
        if toks != r[:len(toks)]:
            bitwise = False
    int_completed = sum(1 for t in t_int
                        if len(out.get(t, [])) == max_new)
    batch_completed = sum(1 for t in t_batch if out.get(t))
    misses = [t for t in gw.timed_out()
              if gw.ticket_info(t)["slo"] == "interactive"
              and not gw.ticket_info(t)["synthetic"]]
    ttfts = sorted(gw.ttft(t) for t in t_int
                   if gw.ttft(t) is not None)
    ttft_p95 = ttfts[min(len(ttfts) - 1,
                         int(0.95 * len(ttfts)))] if ttfts else None

    return {"gateway_storm": {
        "n_interactive": n_int, "n_batch": n_batch,
        "storm_factor": 4, "max_new": max_new,
        "storm_injected":
            _pmetrics.counter("gateway/storm_injected").value - storm0,
        "interactive_completed": int_completed,
        "batch_completed": batch_completed,
        "interactive_deadline_misses": len(misses),
        "interactive_ttft_p95_s":
            round(ttft_p95, 4) if ttft_p95 is not None else None,
        "goodput_rps":
            round((int_completed + batch_completed) / total_s, 2),
        "total_s": round(total_s, 4),
        "shed":
            _pmetrics.counter("gateway/shed").value - shed0,
        "shed_by_class": dict(gw.shed_by_class),
        "deferrals":
            _pmetrics.counter("gateway/deferrals").value - defer0,
        "requeues":
            _pmetrics.counter("serving/requeues").value - requeue0,
        "requeue_exhausted":
            _pmetrics.counter("serving/requeue_exhausted").value
            - exhausted0,
        "brownout_max_level": BROWNOUT_LEVELS[gw.brownout.max_level],
        "brownout_transitions": len(gw.brownout.transitions),
        "bitwise_match": bitwise,
        # SLO engine signals (ISSUE 16): attainment per class, the
        # burn-alert census (resolved = every raised alert cleared by
        # run end), the advisor's verdicts, and the postmortem evidence
        "interactive_slo_attainment":
            (slo_report["per_class"].get("interactive") or {})
            .get("attainment"),
        "slo_attainment_by_class":
            {c: r.get("attainment")
             for c, r in slo_report["per_class"].items()},
        "slo_attainment_by_tenant":
            {k: r.get("attainment")
             for k, r in slo_report["per_tenant"].items()},
        "burn_alerts_raised": alerts_raised,
        "burn_alerts_cleared": alerts_cleared,
        "burn_alerts_resolved":
            (alerts_cleared / alerts_raised) if alerts_raised else 0.0,
        "burn_alert_keys": sorted({f"{a.tenant}/{a.slo_class}"
                                   for a in tracker.alerts}),
        "scale_advice_storm":
            advice_during.action if advice_during else None,
        "scale_advice_after": advice_after.action,
        "headroom_after": advice_after.headroom,
        "timeline_windows": len(tl.windows()),
        "timeline_spilled": len(_ptimeline.load_spill(spill_dir)),
        "flight_prestorm_windows": flight_prestorm,
    }}


def host_dispatch_bench(measure_us):
    """Host-path dispatch cost, shared by bench.py and
    tools/op_bench.py: the same grad-recorded matmul+add dispatches
    against the in-process CPU device isolate the framework's own
    per-op overhead from the accelerator's enqueue cost. The 100/300 us
    bars (enforced by tools/check_op_bench.py) gate THESE numbers. Tiny
    operands on purpose: a 1024^2 matmul would be CPU-compute-bound and
    swamp the dispatch cost being measured.

    measure_us: callable(f) -> steady-state microseconds per call of f.
    """
    import numpy as np

    import paddle_tpu as paddle

    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError as e:
        return {"error": f"no cpu backend: {e}"[:120]}
    rng = np.random.RandomState(0)
    with jax.default_device(cpu):
        xh = paddle.to_tensor(rng.randn(64, 64).astype(np.float32))
        yh = paddle.to_tensor(rng.randn(64, 64).astype(np.float32))
        xh.stop_gradient = False

        def fwd_h():
            return (paddle.matmul(xh, yh) + xh)._value

        def fwdbwd_h():
            z = (paddle.matmul(xh, yh) + xh).sum()
            z.backward()
            g = xh.grad._value
            xh.clear_grad()
            return g

        return {"matmul_add_fwd_us": round(measure_us(fwd_h), 1),
                "matmul_add_fwd_bwd_us": round(measure_us(fwdbwd_h), 1)}


def bench_spec_decode(on_tpu):
    """Speculative decoding gate row (ISSUE 13): a DRAFTABLE
    shared-prompt workload — B greedy requests behind one common system
    prompt whose continuations an NGramDrafter has already observed —
    decoded step-by-step WITH and WITHOUT speculation.  Both sides pay
    one engine dispatch per iteration; the speculative side verifies k
    drafted tokens in that one paged step and emits every accepted one,
    so tokens/s is the accept rate made visible.  ``bitwise_match`` is
    the exactness contract (spec streams identical to the baseline,
    zero slack in benchgate); accept_rate and per-step latency are
    reported so a drafter regression shows up as itself rather than as
    a mystery throughput drop."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              ServingEngine)
    from paddle_tpu.inference.speculative import NGramDrafter

    if on_tpu:
        cfg = PagedServingConfig.llama_1b(
            max_batch=4, num_blocks=4 * 14 + 16, max_blocks_per_seq=14)
        shared_len, tail_len, max_new, k = 96, 4, 128, 8
    else:
        cfg = PagedServingConfig(vocab_size=128, hidden_size=32,
                                 num_layers=2, num_heads=4,
                                 num_kv_heads=2, ffn_size=64,
                                 block_size=8, num_blocks=64,
                                 max_batch=4, max_blocks_per_seq=8,
                                 token_budget=64)
        shared_len, tail_len, max_new, k = 24, 3, 24, 4
    paddle.seed(0)
    with jax.default_device(jax.devices("cpu")[0]):
        model = PagedCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    shared = list(rng.randint(1, cfg.vocab_size, shared_len))
    prompts = [shared + list(rng.randint(1, cfg.vocab_size, tail_len))
               for _ in range(cfg.max_batch)]

    def decode_wave(engine):
        """Submit, prefill to the tip, then time the pure decode loop:
        one engine dispatch per iteration on both sides."""
        rids = [engine.add_request(list(p), max_new_tokens=max_new)
                for p in prompts]
        while any(r.length - r.cached > 1 for r in engine.pending()):
            engine.step()
        t0 = time.perf_counter()
        steps = 0
        while engine.pending():
            engine.step()
            steps += 1
        dt = time.perf_counter() - t0
        out = engine.run_to_completion()
        return [out[r] for r in rids], dt, steps

    # teach wave: serve the workload once, plainly, and let the drafter
    # observe the streams (the prefix-cache-digest block table plus the
    # n-gram table now know every continuation)
    drafter = NGramDrafter(block_size=cfg.block_size)
    ref, _, _ = decode_wave(ServingEngine.from_model(model, cfg, seed=0))
    for p, toks in zip(prompts, ref):
        drafter.observe(list(p) + toks)

    # baseline: warmed non-speculative step loop
    base_out, base_dt, base_steps = decode_wave(
        ServingEngine.from_model(model, cfg, seed=0))

    # speculative: warm wave compiles the verify shapes, second wave is
    # the measured one
    def spec_engine():
        eng = ServingEngine.from_model(model, cfg, seed=0)
        eng.set_drafter(drafter, k=k)
        return eng

    decode_wave(spec_engine())
    eng = spec_engine()
    spec_out, spec_dt, spec_steps = decode_wave(eng)

    n_tok = sum(len(t) for t in spec_out)
    accept = eng._spec_accepted_total / max(eng._spec_drafted_total, 1)
    base_tps = sum(len(t) for t in base_out) / base_dt
    spec_tps = n_tok / spec_dt
    return {"spec_decode": {
        "tokens_per_sec": round(spec_tps, 1),
        "baseline_tokens_per_sec": round(base_tps, 1),
        "speedup": round(spec_tps / base_tps, 3),
        "accept_rate": round(accept, 4),
        "spec_tokens_per_step": round(n_tok / max(spec_steps, 1), 2),
        "step_ms": round(spec_dt / max(spec_steps, 1) * 1e3, 3),
        "baseline_step_ms": round(base_dt / max(base_steps, 1) * 1e3, 3),
        "decode_steps": spec_steps,
        "baseline_decode_steps": base_steps,
        "bitwise_match": 1.0 if spec_out == base_out == ref else 0.0,
        "k": k,
        "drafter": "ngram+block",
        "max_new": max_new,
        "shared_prompt_len": shared_len,
        "batch": cfg.max_batch,
    }}


def bench_weight_publish(on_tpu):
    """Live weight publishing gate row (ISSUE 15): a 3-replica fleet
    serves a continuous wave while a canary-gated int8-free publish
    lands mid-traffic (build -> ship over the CRC'd transport -> canary
    probe of the STAGED version -> fleet promote).  Gate signals, zero
    slack on the first two: every admitted request completes (a rollout
    may never drop traffic), and every stream is token-bitwise-identical
    to a fresh single-engine regeneration under the version it was
    PINNED to — pre-publish streams finish under N, post-publish
    streams run under N+1.  publish_s (build+canary+promote wall time)
    and goodput under the rollout gate with the normal threshold."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.router import Replica, ReplicaRouter
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              SamplingParams,
                                              ServingEngine)
    from paddle_tpu.inference.weight_publish import (WeightPublisher,
                                                     build_weight_set)
    from paddle_tpu.jit import functional as FB

    n_wave, prompt_len, max_new = 5, 12, 6
    cfg = PagedServingConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, ffn_size=64, block_size=8, num_blocks=64,
        max_batch=4, max_blocks_per_seq=6, token_budget=32)
    paddle.seed(0)
    with jax.default_device(jax.devices("cpu")[0]):
        model = PagedCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(1, cfg.vocab_size, prompt_len))
               for _ in range(2 * n_wave)]
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)

    # the candidate version: the serving params plus real perturbation
    # (noise at a few percent of each tensor's scale — enough to change
    # streams, finite enough to pass the canary)
    nrng = np.random.RandomState(5)
    old_params = {k: np.asarray(jax.device_get(v))
                  for k, v in FB.current_params(model).items()}
    new_params = {}
    for k, v in old_params.items():
        if np.issubdtype(v.dtype, np.floating):
            f = v.astype(np.float32)
            new_params[k] = (f + nrng.normal(
                0.0, 0.03 * (np.std(f) + 1e-6), f.shape)
            ).astype(v.dtype)
        else:
            new_params[k] = v

    engines = [ServingEngine.from_model(model, cfg, seed=10 + i)
               for i in range(3)]
    for i, e in enumerate(engines):
        e.fault_rank = i
    router = ReplicaRouter(
        [Replica(e, name=f"r{i}") for i, e in enumerate(engines)])
    pub = WeightPublisher(router, model)

    t0 = time.perf_counter()
    wave_a = [router.submit(list(p), max_new_tokens=max_new, sampling=sp)
              for p in prompts[:n_wave]]
    for _ in range(3):                      # wave A genuinely in flight
        router.step_all()
    report = pub.publish(params=new_params)
    wave_b = [router.submit(list(p), max_new_tokens=max_new, sampling=sp)
              for p in prompts[n_wave:]]
    out = router.run_to_completion()
    total_s = time.perf_counter() - t0

    handles = wave_a + wave_b
    completed = sum(1 for h in handles if len(out.get(h) or []) == max_new)

    # bitwise referee: regenerate every stream on a FRESH single engine
    # holding only its pinned version, under the stream's recorded salt
    # identity — the pinned-version contract made testable
    ref = {0: ServingEngine.from_model(model, cfg, seed=0)}
    arrays, crcs = build_weight_set(model, new_params, cfg)
    ref1 = ServingEngine.from_model(model, cfg, seed=0)
    ref1.stage_weight_set(report.version, arrays, crcs=crcs)
    ref1.commit_weight_set(report.version)
    ref[report.version] = ref1

    def regenerate(prompt, salt_rid, salt_seed, version):
        eng = ref[version]
        rid = eng.add_request(list(prompt), max_new_tokens=max_new,
                              sampling=sp)
        r = eng._requests[rid]
        r.salt_rid, r.salt_seed = salt_rid, salt_seed
        while not r.done:
            eng.step()
        return list(r.generated)

    bitwise = True
    versions_served = set()
    for h, prompt in zip(handles, prompts):
        idx, rid = router._handles[h]
        eng = router.replicas[idx].engine
        r = eng._requests[rid]
        seed = eng.seed if r.salt_seed is None else r.salt_seed
        versions_served.add(r.weight_version)
        if regenerate(prompt, r.salt_rid, seed,
                      r.weight_version) != (out.get(h) or []):
            bitwise = False

    return {"weight_publish": {
        "n_requests": len(handles), "max_new": max_new,
        "requests_completed": completed,
        "bitwise_match": 1.0 if bitwise else 0.0,
        "publish_s": round(report.publish_s, 4),
        "total_s": round(total_s, 4),
        "goodput_rps": round(completed / total_s, 2),
        "version": report.version,
        "versions_served": sorted(versions_served),
        "canary": report.canary,
        "replicas_committed": len(report.committed),
        "replicas_missed": len(report.missed),
        "bytes_shipped": report.bytes_shipped,
    }}


def bench_autoscale_storm(on_tpu):
    """Elastic resize gate row (ISSUE 18): a 2-replica fleet behind the
    gateway meets a 4x admit storm; the AutoScaler grows it to 4 —
    each spawn brought to the fleet's committed weight version (a real
    publish lands BEFORE the storm, so catch-up ships actual weights)
    before entering rotation, with ``kill@spawn`` felling the first
    attempt mid-catch-up (swept + retried, fleet serving throughout) —
    then the post-storm calm drains it back down to 2 while late
    requests are still in flight.  Gate signals, zero slack on the
    first two: every admitted real request completes (a resize may
    never lose traffic) and every stream is token-bitwise-identical to
    a FIXED-FLEET reference run (salt identity rides the stream_key,
    so placement on a spawned replica or a drain off a retiring one
    changes nothing); scale-up reaction time and goodput gate with the
    normal threshold."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.resilience import faults
    from paddle_tpu.inference.autoscaler import (AutoScaler,
                                                 AutoScalerConfig,
                                                 InProcessReplicaFactory)
    from paddle_tpu.inference.fleet_supervisor import FleetSupervisor
    from paddle_tpu.inference.gateway import (BrownoutConfig,
                                              FleetGateway,
                                              GatewayConfig,
                                              SLOClassConfig,
                                              TenantConfig)
    from paddle_tpu.inference.router import Replica, ReplicaRouter
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              SamplingParams,
                                              ServingEngine)
    from paddle_tpu.inference.weight_publish import WeightPublisher
    from paddle_tpu.jit import functional as FB
    from paddle_tpu.profiler import timeline as _ptimeline
    from paddle_tpu.profiler.headroom import ScaleAdvisor

    n_storm, n_calm, prompt_len, max_new = 8, 2, 12, 6
    cfg = PagedServingConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, ffn_size=64, block_size=8, num_blocks=64,
        max_batch=3, max_blocks_per_seq=6, token_budget=32,
        max_queue=8)
    paddle.seed(0)
    with jax.default_device(jax.devices("cpu")[0]):
        model = PagedCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(17)
    storm_prompts = [list(rng.randint(1, cfg.vocab_size, prompt_len))
                     for _ in range(n_storm)]
    calm_prompts = [list(rng.randint(1, cfg.vocab_size, prompt_len))
                    for _ in range(n_calm)]
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)

    # the committed version the spawns must catch up to: the serving
    # params plus finite perturbation (same recipe as weight_publish)
    nrng = np.random.RandomState(7)
    new_params = {}
    for k, v in FB.current_params(model).items():
        a = np.asarray(jax.device_get(v))
        if np.issubdtype(a.dtype, np.floating):
            f = a.astype(np.float32)
            new_params[k] = (f + nrng.normal(
                0.0, 0.03 * (np.std(f) + 1e-6), f.shape)
            ).astype(a.dtype)
        else:
            new_params[k] = a

    def gateway_cfg():
        # all real traffic is protected (the ladder may not clamp or
        # shed it — bitwise gates at zero slack); the storm's synthetic
        # clones are sheddable best-effort
        return GatewayConfig(
            classes={"interactive": SLOClassConfig(priority=0,
                                                   protected=True),
                     "best_effort": SLOClassConfig(priority=2,
                                                   sheddable=True)},
            tenants={"alpha": TenantConfig(rate=500.0, burst=100.0)},
            brownout=BrownoutConfig(enter_load=1.6, exit_load=0.8,
                                    hysteresis=2))

    def build_fleet():
        engines = []
        for i in range(2):
            e = ServingEngine.from_model(model, cfg, seed=30 + i)
            e.fault_rank = i
            engines.append(e)
        router = ReplicaRouter(
            [Replica(e, name=f"r{i}") for i, e in enumerate(engines)])
        sup = FleetSupervisor(
            router, engine_factory=lambda i: ServingEngine.from_model(
                model, cfg, seed=30 + i))
        gw = FleetGateway(router, gateway_cfg())
        pub = WeightPublisher(router, model, supervisor=sup)
        pub.publish(params=new_params)      # committed pre-storm epoch
        return router, sup, gw, pub

    def submit_wave(gw, prompts, key_base):
        return [gw.submit(list(p), max_new_tokens=max_new, sampling=sp,
                          tenant="alpha", slo="interactive",
                          stream_key=key_base + i)
                for i, p in enumerate(prompts)]

    # -- fixed-fleet reference: same publish, no storm, no resize
    faults.disarm()
    _, _, gw_ref, _ = build_fleet()
    t_ref = submit_wave(gw_ref, storm_prompts, 1000) \
        + submit_wave(gw_ref, calm_prompts, 2000)
    out_ref = gw_ref.run_to_completion(max_steps=4000)
    ref = {gw_ref.ticket_info(t)["stream_key"]: out_ref.get(t, [])
           for t in t_ref}

    # -- the live run: storm + resize under chaos
    step_count = [0]
    tl = _ptimeline.Timeline(clock=lambda: float(step_count[0]))
    advisor = ScaleAdvisor(tl, window_s=30.0, min_windows=2,
                           high_load=0.8, low_load=0.3)
    router, sup, gw, pub = build_fleet()
    factory = InProcessReplicaFactory(model, cfg, seed_base=100)
    scaler = AutoScaler(
        router, sup, advisor, factory,
        AutoScalerConfig(min_replicas=2, max_replicas=4,
                         scale_up_after=2, scale_down_after=2,
                         cooldown_evals=2, catchup_timeout_s=10.0,
                         max_spawn_failures=3, spawn_backoff_base_s=0.0,
                         spawn_backoff_cap_s=0.0),
        gateway=gw, publisher=pub)
    _ptimeline.install(tl)

    def tick(every: int = 3):
        step_count[0] += 1
        if step_count[0] % every == 0:
            tl.sample()
            scaler.evaluate()

    scaleup_s = None
    try:
        # kill@spawn#1: the FIRST spawn attempt dies mid-catch-up and
        # is swept; overload@admit turns every arrival into 4
        faults.arm("kill@spawn#1,overload@admit%1.0:x=4")
        t0 = time.perf_counter()
        tickets = submit_wave(gw, storm_prompts, 1000)
        for _ in range(4000):
            gw.step()
            tick()
            if scaleup_s is None and router.fleet_size() > 2:
                scaleup_s = time.perf_counter() - t0
            if not gw.queued() and not gw.router._live_pending():
                break
        faults.disarm()
        peak_size = router.fleet_size()

        # calm: late traffic still in flight while the fleet shrinks
        tickets += submit_wave(gw, calm_prompts, 2000)
        for _ in range(2000):
            gw.step()
            tick()
            if router.fleet_size() <= 2 and not gw.queued() \
                    and not gw.router._live_pending():
                break
        out = gw.results()
        total_s = time.perf_counter() - t0
    finally:
        faults.disarm()
        _ptimeline.uninstall(tl)

    completed = sum(1 for t in tickets
                    if len(out.get(t) or []) == max_new)
    bitwise = all(
        (out.get(t) or []) == ref.get(gw.ticket_info(t)["stream_key"])
        for t in tickets)
    actions = [r for r in scaler.history
               if r["action"] in ("scale_up", "scale_down")]
    return {"autoscale_storm": {
        "n_requests": len(tickets), "max_new": max_new,
        "requests_completed": completed,
        "bitwise_match": 1.0 if bitwise else 0.0,
        "scaleup_to_traffic_s": round(scaleup_s, 4)
        if scaleup_s is not None else None,
        "goodput_rps": round(completed / total_s, 2),
        "total_s": round(total_s, 4),
        "peak_fleet": peak_size,
        "final_fleet": router.fleet_size(),
        "spawn_failures": scaler.spawn_failures,
        "actions": [{"action": r["action"], "size": r["size"]}
                    for r in actions],
        "committed_version": pub.version,
    }}


def bench_eager_dispatch(on_tpu):
    """Eager per-op dispatch cost through the per-signature jit cache
    (VERDICT r2 #1; reference analog: the all-C++ eager hot path,
    eager/auto_code_generator/generator/python_c_gen.py:111). Reports
    steady-state µs/iter for grad-recorded matmul(1024²)+add and for a
    full fwd+bwd, far from the 5,447 µs/iter of the uncached funnel."""
    import paddle_tpu as paddle
    from paddle_tpu.core import dispatch as _dispatch

    n = 100 if on_tpu else 30
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(1024, 1024).astype(np.float32))
    y = paddle.to_tensor(rng.randn(1024, 1024).astype(np.float32))
    x.stop_gradient = False

    def fwd():
        return (paddle.matmul(x, y) + x)._value

    def fwdbwd():
        z = (paddle.matmul(x, y) + x).sum()
        z.backward()
        g = x.grad._value
        x.clear_grad()
        return g

    def measure(f):
        # dispatch throughput: drain the queue, then time n async
        # enqueues per window.  The reported number is the MEDIAN over 5
        # windows after a longer warm-up — a min-of-3 windows is one GC
        # pause away from either tail; the median is stable against a
        # single bad (or single lucky) window.  The min/max spread is
        # reported alongside so instability stays visible.
        for _ in range(10):
            jax.device_get(f())   # warm: legacy + trace + steady + JIT
        windows = []
        for _ in range(5):
            jax.device_get(f())   # drain
            t0 = time.perf_counter()
            for _ in range(n):
                f()
            windows.append((time.perf_counter() - t0) / n)
        t0 = time.perf_counter()
        jax.device_get(f())
        sync_ms = (time.perf_counter() - t0) * 1e3
        windows.sort()
        med = windows[len(windows) // 2]
        return med * 1e6, sync_ms, (windows[0] * 1e6, windows[-1] * 1e6)

    fwd_us, _, fwd_spread = measure(fwd)
    fwdbwd_us, sync_ms, fwdbwd_spread = measure(fwdbwd)

    host = host_dispatch_bench(lambda f: measure(f)[0])
    return {"matmul_add_fwd_us": round(fwd_us, 1),
            "matmul_add_fwd_bwd_us": round(fwdbwd_us, 1),
            "fwd_us_window_minmax": [round(v, 1) for v in fwd_spread],
            "fwd_bwd_us_window_minmax": [round(v, 1)
                                         for v in fwdbwd_spread],
            "host_path": host,
            "queue_drain_ms": round(sync_ms, 1),
            "op_cache": _dispatch.op_cache_stats()}


def bench_second_order(on_tpu):
    """paddle.grad(create_graph=True) composed with the whole-sweep
    cached eager backward at Llama-block dims (VERDICT r4 #9): a
    WGAN-GP-style gradient penalty — grad of the output w.r.t. the input
    builds a second graph that backward() then differentiates — must
    ride the per-signature jit cache (entries stable across steps, no
    retrace) at real dims on the chip."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.core.dispatch import op_cache_stats

    if on_tpu:
        h, f, tokens, n = 2048, 5632, 256, 8
    else:
        h, f, tokens, n = 32, 64, 8, 2
    paddle.seed(0)
    with jax.default_device(jax.devices("cpu")[0]):
        model = nn.Sequential(nn.Linear(h, f), nn.Silu(),
                              nn.Linear(f, h))
    opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                learning_rate=1e-4)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(tokens, h).astype(np.float32))

    def step():
        x.stop_gradient = False
        out = model(x)
        (g,) = paddle.grad([out.sum()], [x], create_graph=True)
        gp = ((g.pow(2).sum(axis=-1) + 1e-12).sqrt() - 1.0).pow(2).mean()
        loss = out.mean() + 10.0 * gp
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    loss = step()
    jax.device_get(loss._value)
    loss = step()                      # steady-state signature
    jax.device_get(loss._value)
    entries_before = op_cache_stats()["entries"]

    def window():
        nonlocal loss
        for _ in range(n):
            loss = step()

    dt = best_of(2, window, lambda: jax.device_get(loss._value))
    stats = op_cache_stats()
    return {"grad_penalty_step_ms": round(dt / n * 1e3, 2),
            "tokens": tokens, "hidden": h, "ffn": f,
            "cache_entries_steady": stats["entries"] == entries_before,
            "op_cache": stats,
            "loss": float(jax.device_get(loss._value))}


def bench_llama_train(on_tpu):
    """Flagship row: compiled stacked-Llama train step on one chip."""
    from jax.sharding import Mesh

    from paddle_tpu.distributed.fleet.trainer import HybridTrainer
    from paddle_tpu.models import llama

    if on_tpu:
        # Llama-2-native 4k context: measured MFU 0.6155 vs 0.6012 at
        # seq 2048 (longer seq = more attention FLOPs through the Pallas
        # flash kernel)
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=4096,
            dtype="bfloat16", recompute=True)
        batch, seq, steps = 4, 4096, 10
    else:  # CPU smoke fallback so the harness never hard-fails
        cfg = llama.LLAMA_PRESETS["debug"]
        batch, seq, steps = 2, 128, 3

    dev = np.asarray(jax.devices()[:1]).reshape(1, 1, 1, 1, 1)
    mesh = Mesh(dev, ("dp", "pp", "sharding", "sep", "mp"))
    trainer = HybridTrainer(cfg, mesh, learning_rate=3e-4)

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(trainer.params))

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)

    # compile + warmup
    loss = trainer.step(ids, labels)
    jax.block_until_ready(loss)

    def window():
        nonlocal loss
        for _ in range(steps):
            loss = trainer.step(ids, labels)

    dt = best_of(2, window, lambda: jax.device_get(loss))

    tokens_per_sec = batch * seq * steps / dt
    flops_per_token = model_flops_per_token(cfg, n_params, seq)
    mfu = tokens_per_sec * flops_per_token / peak_flops_per_chip()
    return {"tokens_per_sec_per_chip": round(tokens_per_sec, 1),
            "mfu": round(mfu, 4), "n_params": n_params, "batch": batch,
            "seq": seq, "steps": steps,
            "loss": float(jax.device_get(loss))}


def bench_autotune_rank(on_tpu):
    """Static auto-tuner row: rank the full (dp, pp, sharding, mp,
    recompute) grid for the llama-block capture from sharding
    propagation alone — no compile, no device.  Gated on configs_ranked
    and Pareto consistency of the top pick vs the MULTICHIP
    dryrun-validated configs (both zero-slack)."""
    import time as _time

    from paddle_tpu.analysis.program.capture import PRESETS
    from paddle_tpu.analysis.sharding import graph_from_program
    from paddle_tpu.distributed.auto_tuner import (
        StaticAutoTuner, top_is_pareto_consistent)

    cap = PRESETS["llama-block"]()
    g = graph_from_program(cap.program, cap.feed_spec, name=cap.name)
    tuner = StaticAutoTuner(g)
    tuner.rank()                                    # warm caches
    t0 = _time.perf_counter()
    ranked = tuner.rank()
    rank_ms = (_time.perf_counter() - t0) * 1e3
    return {"autotune_rank": {
        "rank_ms": round(rank_ms, 2),
        "configs_ranked": len(ranked),
        "pareto_consistent":
            1.0 if top_is_pareto_consistent(ranked) else 0.0,
        "top_config": ranked[0].config.describe(),
        "top_step_ms": round(ranked[0].est_step_ms, 3),
    }}


# (name, fn, gate_row): gate rows run under --fast too — they feed the
# tools/benchgate.py regression gate (tokens/s-per-chip, ttft/tpot,
# dispatch µs); the rest only run under --full
WORKLOADS = (
    ("llama_train", bench_llama_train, True),
    ("resnet50_dp", bench_resnet50, False),
    ("bert_base_pretrain", bench_bert, False),
    ("sd_unet", bench_sd_unet, False),
    ("eager_dispatch", bench_eager_dispatch, True),
    ("llama13b_block", bench_llama13b_block, False),
    ("serving", bench_serving, True),
    ("spec_decode", bench_spec_decode, True),
    ("fleet", bench_fleet_serving, True),
    ("fleet_recovery", bench_fleet_recovery, True),
    ("host_recovery", bench_host_recovery, True),
    ("fleet_subprocess", bench_fleet_subprocess, True),
    ("weight_publish", bench_weight_publish, True),
    ("gateway_storm", bench_gateway_storm, True),
    ("autoscale_storm", bench_autoscale_storm, True),
    ("autotune_rank", bench_autotune_rank, True),
    ("second_order", bench_second_order, False),
)


def assemble_final(rows, mode="full"):
    """Build the final JSON of record from whatever rows finished —
    timed-out / errored workloads stay visible as their partial rows
    instead of killing the run (the r05 rc-124 failure mode)."""
    llama = rows.get("llama_train") or {}
    tps = llama.get("tokens_per_sec_per_chip")
    mfu = llama.get("mfu")
    result = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": tps,
        "unit": "tokens/s",
        # single-chip Llama MFU vs the 0.45 north-star target; the target
        # is defined for Llama-13B on v5p-128 — same metric, easier
        # (single-chip) regime, stated here honestly as a proxy
        "vs_baseline": round(mfu / 0.45, 4) if mfu is not None else None,
        "extra": {
            "mfu": mfu,
            "n_params": llama.get("n_params"),
            "batch": llama.get("batch"),
            "seq": llama.get("seq"),
            "steps": llama.get("steps"),
            "loss": llama.get("loss"),
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "mode": mode,
            "vs_baseline_semantics":
                "single-chip MFU proxy for the v5p-128 13B target",
        },
    }
    for name, payload in rows.items():
        if name != "llama_train":
            result["extra"][name] = payload
    if isinstance(llama, dict) and (llama.get("timed_out")
                                    or llama.get("error")):
        # flagship row failed: keep the raw partial row visible instead
        # of silently flattening it into null fields
        result["extra"]["llama_train"] = llama
    incomplete = sorted(
        name for name, payload in rows.items()
        if isinstance(payload, dict)
        and (payload.get("timed_out") or payload.get("error")))
    if incomplete:
        result["extra"]["incomplete_rows"] = incomplete
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true",
                    help="regression-gate rows only (llama train, eager "
                         "dispatch, serving)")
    ap.add_argument("--full", action="store_true",
                    help="every workload (default)")
    ap.add_argument("--timeout-s", type=float,
                    default=float(os.environ.get("PT_BENCH_TIMEOUT_S",
                                                 "900")),
                    help="per-workload budget in seconds (0 disables)")
    args = ap.parse_args(argv)
    mode = "fast" if args.fast and not args.full else "full"

    from paddle_tpu.core.place import on_tpu as _on_tpu
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    on_tpu = _on_tpu()
    reset_partial()
    # crash-safe metrics: periodic atomic snapshots next to the bench
    # results, so a timed-out run still shows what the framework did
    try:
        from paddle_tpu.profiler import metrics as _metrics

        _metrics.enable_periodic_flush(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_metrics.json"), interval_s=15.0)
    except Exception:
        _metrics = None

    import gc

    rows = {}

    def run_row(name, fn):
        """One bench row under the per-workload budget: never kills the
        run, and its result hits BENCH_partial.jsonl the moment it
        finishes (or times out)."""
        t0 = time.perf_counter()
        try:
            payload = run_with_timeout(lambda: fn(on_tpu),
                                       args.timeout_s)
        except WorkloadTimeout:
            payload = {"timed_out": True,
                       "timeout_s": args.timeout_s,
                       "elapsed_s": round(time.perf_counter() - t0, 1)}
        except Exception as e:
            payload = {"error": str(e)[:200]}
        emit_partial(name, payload)
        rows[name] = payload
        # free params/opt state (the llama trainer alone holds ~10GB)
        # before the next model compiles
        gc.collect()
        jax.clear_caches()
        return payload

    for name, fn, gate_row in WORKLOADS:
        if mode == "fast" and not gate_row:
            continue
        run_row(name, fn)

    result = assemble_final(rows, mode)
    emit_partial("final", result)
    if _metrics is not None:
        _metrics.disable_periodic_flush()   # final atomic snapshot
    print(json.dumps(result))


if __name__ == "__main__":
    main()

"""Continuous-batching serving engine over the paged-KV cache.

Reference analog: the Paddle Inference serving stack
(paddle/fluid/inference/api/analysis_predictor.cc) driving the
block-attention serving kernels
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention.cu): N
concurrent requests share one decoder executable; each engine step packs
a mixed batch of prefill and decode tokens, attends against paged KV
blocks addressed by per-request block tables, and requests join/leave
the batch at any step (continuous batching).

TPU-native shape: the WHOLE step function — embedding, L llama-style
decoder layers (RMSNorm, GQA `block_multihead_attention`, swiglu) and
the LM head — is one executable with static shapes (token budget, max
batch, fixed page pool), either exported through the
`save_inference_model` artifact or jitted directly from a live model
(`ServingEngine.from_model`). The host side (`ServingEngine`) is only a
scheduler: page allocator + request queue + chunked prefill. Sampling
(greedy / temperature / top-k / top-p) runs ON DEVICE with
schedule-independent RNG salts, so paged-engine generations reproduce
the dense reference path token-for-token under the same seed. Padding
tokens are routed to a reserved trash page so the static token budget
never corrupts live cache pages.

Per-step host work is O(batch); `decode_run` additionally amortises the
host round-trip over many decode steps (tokens are fed device-to-device
between steps, one sync per window) — the multi-step scheduling trick
production engines use, essential over high-latency links.
"""
from __future__ import annotations

import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from ..core.dispatch import apply
from ..profiler import RecordEvent, host_tracing_active
from ..profiler import metrics as _metrics
from ..profiler import scopes as _scopes
from ..profiler import tracing as _tracing


class _EngineMetrics:
    """Handle bundle for the serving/* series one engine writes: TTFT
    from request submit (or the arrival time its caller passed) to its
    first sampled token, TPOT once a request finishes ((last - first
    token) / (tokens - 1); decode_run observes window wall / steps
    instead), plus scheduler gauges the
    capacity story needs. Built from a registry so a fleet replica can
    bind its engine to a per-replica child registry (writes roll up to
    the global one) instead of conflating co-hosted replicas in the
    process-wide series — see ServingEngine.set_metrics_namespace."""

    __slots__ = ("ttft", "tpot", "steps", "paged_steps", "inplace_steps",
                 "tokens",
                 "requests", "step_rows", "step_tokens", "step_pad",
                 "step_prefill", "preempt", "occupancy", "kv_util",
                 "deadline", "shed",
                 "prefix_rate", "prefix_pages", "spec_steps",
                 "spec_drafted", "spec_accepted", "spec_accept_rate",
                 "spec_tokens_per_step", "fused_regions",
                 "weight_version", "weight_swaps", "weight_rollbacks",
                 "ssm_decode", "ssm_chunk", "ssm_resets", "step_counts",
                 "lookahead", "settle_forced")

    def __init__(self, reg, step_counters=()):
        self.ttft = reg.histogram("serving/ttft_ms")
        self.tpot = reg.histogram("serving/tpot_ms")
        self.steps = reg.counter("serving/steps")
        # of them, the steps whose program holds the paged-attention
        # kernel (ops/pallas/paged_attention.py); 0 on an engine that
        # traces off the chip or loads an exported artifact
        self.paged_steps = reg.counter("serving/paged_kernel_steps")
        # and the steps whose program writes the step's keys and values
        # into the donated page stacks where they lie
        # (ops/pallas/kv_page_write.py): a fresh-prefill step too
        self.inplace_steps = reg.counter("serving/kv_inplace_steps")
        # what each step held (bumped once a step): scheduled rows, real
        # tokens, the padding up to the step's static token length, and
        # the tokens of rows still inside their prompt
        self.step_rows = reg.counter("serving/step_rows")
        self.step_tokens = reg.counter("serving/step_tokens")
        self.step_pad = reg.counter("serving/step_pad_tokens")
        self.step_prefill = reg.counter("serving/step_prefill_tokens")
        # of serving/steps, those dispatched while the step before them
        # was still unsettled (the chip had its next step queued), and
        # the settles something other than the next step asked for
        # (ServingEngine.settle)
        self.lookahead = reg.counter("serving/lookahead_steps")
        self.settle_forced = reg.counter("serving/settle_forced")
        self.tokens = reg.counter("serving/tokens_generated")
        self.requests = reg.counter("serving/requests")
        self.preempt = reg.counter("serving/preemptions")
        self.occupancy = reg.gauge("serving/batch_occupancy")
        self.kv_util = reg.gauge("serving/kv_cache_utilization")
        self.deadline = reg.counter("serving/deadline_evictions")
        self.shed = reg.counter("serving/load_shed")
        self.prefix_rate = reg.gauge("serving/prefix_hit_rate")
        self.prefix_pages = reg.counter("serving/prefix_pages_reused")
        # speculative decoding (inference/speculative.py + _spec_step)
        self.spec_steps = reg.counter("serving/spec_steps")
        self.spec_drafted = reg.counter("serving/spec_drafted_tokens")
        self.spec_accepted = reg.counter("serving/spec_accepted_tokens")
        self.spec_accept_rate = reg.gauge("serving/spec_accept_rate")
        self.spec_tokens_per_step = reg.gauge(
            "serving/spec_tokens_per_step")
        # distinct whole-iteration decode executables this engine built
        # (decode windows + speculative verify shapes)
        self.fused_regions = reg.counter("compiler/fused_decode_regions")
        # live weight publishing (inference/weight_publish.py): the
        # version this engine currently serves, atomic swaps taken, and
        # rollbacks to the retained previous buffer
        self.weight_version = reg.gauge("serving/weight_version")
        self.weight_swaps = reg.counter("serving/weight_swaps")
        self.weight_rollbacks = reg.counter("serving/weight_rollbacks")
        # state-space layers (a model with row state, layer_states.py):
        # rows a step sent through the one-token state update, rows it
        # sent through the chunked scan, and rows that started from a
        # zeroed slot (a new or a re-prefilled request)
        self.ssm_decode = reg.counter("serving/ssm_rows_decode")
        self.ssm_chunk = reg.counter("serving/ssm_rows_chunk")
        self.ssm_resets = reg.counter("serving/ssm_state_resets")
        # counts the model's step returns from the device, under the
        # names the model gives them (LayerStates.counters), added where
        # the step's tokens are fetched
        self.step_counts = [reg.counter(name) for name in step_counters]

__all__ = ["PagedServingConfig", "PagedCausalLM", "ServingEngine",
           "SamplingParams", "save_paged_model", "sampling_salt",
           "sample_logits", "EngineOverloadedError"]


class EngineOverloadedError(RuntimeError):
    """Admission rejected: the engine is saturated (queue at max_queue).
    The serving front-end should shed this request (HTTP 429 / retry on
    another replica) rather than let it age out against its deadline
    deep in an unbounded queue."""


def resolve_backend_device(backend):
    """Resolve ``PagedServingConfig.backend`` to a concrete device.

    ``None`` keeps the ambient default (resolution deferred to jax —
    exactly the pre-seam behavior); a string names a platform and
    resolves to its first device (``jax.devices(backend)[0]``); a
    ``jax.Device`` passes through.  The single place engine
    construction turns a backend HANDLE into placement, so
    heterogeneous fleets (cpu/tpu/plugin replicas behind one router)
    differ only in the handle their factory threads through."""
    if backend is None:
        return None
    if isinstance(backend, str):
        devs = jax.devices(backend)
        if not devs:
            raise ValueError(f"backend {backend!r} has no devices")
        return devs[0]
    return backend


class PagedServingConfig:
    """Engine/model dims for the paged-KV serving path.

    ``cache_quant="int8"`` stores KV pages as int8 with per-(token,
    head) dynamic scales: cache bytes halve, so the same HBM holds about
    twice the pages (longer contexts, more sequences before preemption),
    at the price of quantize-on-append and dequantize-on-read work in
    every step. No benchmark cell runs an int8 cache, so its step time
    against bf16 is not measured. On a TPU the mixed,
    verify and decode-window steps of a bf16/float32 cache attend through
    the paged-attention Pallas kernel (ops/pallas/paged_attention.py);
    an int8 cache takes the gathered jnp reference instead (the kernel
    reads floating pages), counted as
    `pallas/reference_dispatch/paged_attention`.
    """

    def __init__(self, vocab_size=256, hidden_size=64, num_layers=2,
                 num_heads=4, ffn_size=128, block_size=16, num_blocks=64,
                 max_batch=4, max_blocks_per_seq=8, token_budget=64,
                 num_kv_heads=None, dtype="float32", cache_quant=None,
                 max_queue=None, prefix_cache=False,
                 prefix_snapshot_root=None, prefix_page_quota=None,
                 backend=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.head_dim = hidden_size // num_heads
        self.ffn_size = ffn_size
        self.block_size = block_size
        self.num_blocks = num_blocks          # page pool (page 0 = trash)
        self.max_batch = max_batch
        self.max_blocks_per_seq = max_blocks_per_seq
        self.token_budget = token_budget
        self.dtype = dtype
        # cache_quant="int8": pages stored int8 with per-(token, head)
        # dynamic scales — cache memory and HBM decode traffic halve
        if cache_quant not in (None, "int8"):
            raise ValueError("cache_quant must be None or 'int8'")
        self.cache_quant = cache_quant
        # load shedding: admission is rejected (EngineOverloadedError)
        # once this many requests are live; None = admit everything
        self.max_queue = max_queue
        # prefix_cache=True: requests sharing a prompt prefix map their
        # leading full blocks to the same physical pages (refcounted trie
        # over the page pool, see inference/prefix_cache.py) — a cache
        # hit skips straight past the shared tokens' prefill
        self.prefix_cache = bool(prefix_cache)
        # prefix_snapshot_root: directory of cache_<seq> snapshot dirs.
        # An engine built with this set restores the newest complete
        # snapshot at start (a restarted replica serves warm shared-
        # prefix hits immediately) and save_prefix_cache() snapshots
        # there by default.
        self.prefix_snapshot_root = prefix_snapshot_root
        # prefix_page_quota: default per-tenant-namespace cap on cache
        # pages OWNED (prefix_cache.py quotas; None = unbounded) — the
        # gateway overrides per tenant via PrefixCache.set_quota
        self.prefix_page_quota = prefix_page_quota
        # backend: an EXPLICIT placement handle for engine construction
        # — a jax.Device, a platform name ("cpu"/"tpu"/a PJRT plugin),
        # or None for the process-ambient default (unchanged behavior).
        # A ReplicaFactory building a heterogeneous fleet sets this per
        # replica instead of relying on whatever jax.devices() happens
        # to return first (resolve_backend_device).
        self.backend = backend
        self.max_seq = max_blocks_per_seq * block_size


class SamplingParams:
    """Per-request decode sampling. temperature<=0 means greedy (argmax);
    top_k<=0 and top_p>=1 disable those filters. Reference analog: the
    sampling layers of the fused-generation serving path
    (paddle/phi/kernels/fusion/gpu — top_p_sampling kernels)."""

    def __init__(self, temperature=0.0, top_k=0, top_p=1.0):
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)


GREEDY = SamplingParams()


def sampling_salt(seed, rid, n_generated):
    """Schedule-independent RNG salt for one sampled token: depends only
    on (engine seed, request id, index of the token being sampled), so
    chunked prefill, preemption, batching order and the dense reference
    path all draw identical randomness."""
    return (seed * 1000003 + rid * 65537 + n_generated) & 0x7FFFFFFF


def _sample_core(logits, temps, topks, topps, salts):
    """Batched device-side sampling: greedy when temp<=0, else
    gumbel-argmax over temperature-scaled logits restricted to the
    top-k/top-p support. Gumbel noise is indexed by TOKEN ID (not sorted
    rank) so near-tie sort-order differences between two numerically
    close logit sources cannot change the draw."""
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    base = jax.random.key(0)

    def row(lg, t, k, p, s):
        greedy = jnp.argmax(lg)
        lt = lg / jnp.maximum(t, 1e-6)
        order = jnp.argsort(-lt)
        sl = lt[order]
        ranks = jnp.arange(V)
        keep = jnp.where(k > 0, ranks < k, True)
        pr = jax.nn.softmax(jnp.where(keep, sl, -jnp.inf))
        keep = keep & ((jnp.cumsum(pr) - pr) < p)   # excl-cumsum keeps >=1
        keep_tok = jnp.zeros((V,), bool).at[order].set(keep)
        g = jax.random.gumbel(jax.random.fold_in(base, s), (V,),
                              jnp.float32)
        sampled = jnp.argmax(jnp.where(keep_tok, lt, -jnp.inf) + g)
        return jnp.where(t <= 0.0, greedy, sampled).astype(jnp.int32)

    return jax.vmap(row)(logits, temps.astype(jnp.float32),
                         topks.astype(jnp.int32),
                         topps.astype(jnp.float32),
                         salts.astype(jnp.int32))


_TOPK_FAST_C = 128


def _sample_topk_core(logits, temps, topks, topps, salts):
    """Fast sampler for the common serving regime: every sampling row has
    0 < top_k <= _TOPK_FAST_C. `lax.top_k` over C candidates replaces the
    full-vocab sort (the 32k-sort dominates a bf16 decode step on TPU).
    EXACT vs `_sample_core`: the top-p filter is applied inside the top-k
    support (so the kept set is identical for k <= C), candidate values
    equal the sorted values, and gumbel noise is keyed by TOKEN ID, so
    the argmax winner is the same token."""
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    C = min(_TOPK_FAST_C, V)       # C == V degenerates to the full set
    base = jax.random.key(0)

    def row(lg, t, k, p, s):
        greedy = jnp.argmax(lg)
        lt = lg / jnp.maximum(t, 1e-6)
        vals, idx = jax.lax.top_k(lt, C)                 # ties: low index
        keep = jnp.arange(C) < k
        pr = jax.nn.softmax(jnp.where(keep, vals, -jnp.inf))
        keep = keep & ((jnp.cumsum(pr) - pr) < p)
        g = jax.random.gumbel(jax.random.fold_in(base, s), (V,),
                              jnp.float32)
        win = jnp.argmax(jnp.where(keep, vals, -jnp.inf) + g[idx])
        return jnp.where(t <= 0.0, greedy, idx[win]).astype(jnp.int32)

    return jax.vmap(row)(logits, temps.astype(jnp.float32),
                         topks.astype(jnp.int32),
                         topps.astype(jnp.float32),
                         salts.astype(jnp.int32))


def _topk_fast_ok(temps, topks):
    """True when every sampling row is within the exact top-k fast path."""
    sampling = temps > 0
    return bool(np.all(~sampling | ((topks > 0)
                                    & (topks <= _TOPK_FAST_C))))


def _next_pow2(n):
    """Smallest power of two >= n (n >= 1) — the shape-bucketing unit
    that bounds decode/verify retraces at log2 distinct executables."""
    return 1 << (int(n) - 1).bit_length()


def _sampler(name, core):
    """A sampler as a program of its own: a named function, so a device
    trace's "XLA Modules" line reads `jit_serving_sample_greedy`, not
    `jit__lambda`, and its operations carry the `pt.sample` scope."""
    def fn(*args):
        with _scopes.scope("sample"):
            return core(*args)

    fn.__name__ = name
    return jax.jit(fn)


_greedy_tokens_dev = _sampler(
    "serving_sample_greedy",
    lambda logits: jnp.argmax(logits, axis=-1).astype(jnp.int32))
_sample_tokens_dev = _sampler("serving_sample", _sample_core)
_sample_topk_dev = _sampler("serving_sample_topk", _sample_topk_core)


def serving_feed_tokens(prev_sampled, src, host_tokens):
    """A step's packed tokens where some are still on the device: position
    p takes the unsettled step's sampled token of row `src[p]`, or the
    host's token where `src[p]` is -1. The engine's own program, so the
    model's step programs see a token vector as they always did."""
    return jnp.where(src >= 0, prev_sampled[jnp.maximum(src, 0)],
                     host_tokens)


_feed_tokens_dev = jax.jit(serving_feed_tokens)


def sample_logits(logits, sampling: SamplingParams, salt: int) -> int:
    """Sample one token from a single logits vector with the engine's
    exact sampler — the reference-path helper for parity tests."""
    out = _sample_tokens_dev(
        jnp.asarray(logits)[None], jnp.asarray([sampling.temperature]),
        jnp.asarray([sampling.top_k]), jnp.asarray([sampling.top_p]),
        jnp.asarray([salt]))
    return int(np.asarray(out)[0])


# PagedCausalLM._step_mode -> the name of the step program traced under it
_STEP_PROGRAMS = {None: "serving_step",
                  "fresh_prefill": "serving_fresh_prefill",
                  "spec_verify": "serving_spec_verify"}


class PagedCausalLM(Layer):
    """A llama-architecture causal LM (RMSNorm → GQA attention → swiglu
    MLP, untied LM head, no biases — models/llama.py at serving time)
    whose serving forward runs entirely on paged KV caches via
    block_multihead_attention. `forward` is the exported step function;
    `forward_dense` is the stateless reference path over the SAME weights
    (used to validate engine generations)."""

    def __init__(self, cfg: PagedServingConfig):
        super().__init__()
        from .. import nn

        self.cfg = cfg
        h, f, D = cfg.hidden_size, cfg.ffn_size, cfg.head_dim
        kvw = cfg.num_kv_heads * D
        self.embed = nn.Embedding(cfg.vocab_size, h)
        self.ln1 = nn.LayerList([nn.RMSNorm(h)
                                 for _ in range(cfg.num_layers)])
        self.qkv = nn.LayerList([nn.Linear(h, h + 2 * kvw,
                                           bias_attr=False)
                                 for _ in range(cfg.num_layers)])
        self.proj = nn.LayerList([nn.Linear(h, h, bias_attr=False)
                                  for _ in range(cfg.num_layers)])
        self.ln2 = nn.LayerList([nn.RMSNorm(h)
                                 for _ in range(cfg.num_layers)])
        self.gate_up = nn.LayerList([nn.Linear(h, 2 * f, bias_attr=False)
                                     for _ in range(cfg.num_layers)])
        self.down = nn.LayerList([nn.Linear(f, h, bias_attr=False)
                                  for _ in range(cfg.num_layers)])
        self.ln_f = nn.RMSNorm(h)
        self.head = nn.Linear(h, cfg.vocab_size, bias_attr=False)

    def layer_states(self):
        """What the layers keep for a request: pages, in every layer."""
        from .layer_states import LayerStates

        return LayerStates.attention_only(self.cfg)

    def _lin(self, kind, li, h, w):
        """One decoder Linear (bias-free): the layer's own weight, or —
        when an int8 weight streamer is live (``w`` holds the layer's
        dequantized group, prefetched while the PREVIOUS layer computed)
        — a plain matmul against the streamed weight."""
        if w is None:
            return getattr(self, kind)[li](h)
        mat = w[kind]

        def mm(a):
            return a @ mat

        return apply(mm, h, op_name="stream_linear")

    def _mlp(self, li, h, w=None):
        from ..incubate.nn.functional import swiglu

        gu = self._lin("gate_up", li, h, w)
        half = self.cfg.ffn_size

        def split(a):
            return a[..., :half], a[..., half:]

        g, u = apply(split, gu, op_name="split_gate_up")
        return self._lin("down", li, swiglu(g, u), w)

    # -- rope table shared by both paths ---------------------------------
    def _rope_table(self, positions):
        """(cos, sin) [..., head_dim//2] at absolute positions."""
        half = self.cfg.head_dim // 2
        inv = 1.0 / (10000.0 ** (
            jnp.arange(half, dtype=jnp.float32) * 2.0 / self.cfg.head_dim))
        ang = positions[..., None].astype(jnp.float32) * inv
        return jnp.cos(ang), jnp.sin(ang)

    # -- exported paged step ---------------------------------------------
    def forward(self, tokens, seq_lens_encoder, seq_lens_decoder,
                seq_lens_this_time, cu_seqlens_q, block_tables,
                key_caches, value_caches, k_scales=None, v_scales=None):
        """One engine step.

        tokens [T] int32 packed (each scheduled row contributes its
        chunk of seq_lens_this_time[b] tokens starting at cache position
        seq_lens_decoder[b]; padding routed to the trash row);
        seq_lens_* [B+1] (last row is the padding row); cu_seqlens_q
        [B+2]; block_tables [B+1, max_blocks]; key/value_caches
        [L, num_blocks, HKV, bs, D]. Returns (last-token logits [B+1, V],
        new key_caches, new value_caches).
        """
        from ..incubate.nn import functional as IF

        cfg = self.cfg
        with _scopes.scope("embed"):
            x = self.embed(tokens)                           # [T, H]
        # batch/seq dims come from the INPUTS, not cfg: one model serves
        # engines of different max_batch/max_seq (each jit-specializes)
        B1 = int(seq_lens_encoder.shape[0])
        max_seq = int(block_tables.shape[1]) * cfg.block_size

        def rope_emb_arg():
            pos = jnp.arange(max_seq)
            cos, sin = self._rope_table(pos)                 # [S, D/2]
            cos = jnp.broadcast_to(cos[None], (B1,) + cos.shape)
            sin = jnp.broadcast_to(sin[None], (B1,) + sin.shape)
            return Tensor(jnp.stack([cos, sin])
                          .reshape(2, B1, 1, max_seq, cfg.head_dim
                                   // 2))

        rope = apply(rope_emb_arg, op_name="rope_table")
        new_kc, new_vc = key_caches, value_caches
        new_ks, new_vs = k_scales, v_scales
        quant = k_scales is not None
        # int8 weight streaming (inference/weight_stream.py): dequantize
        # layer i+1's Linear group BEFORE layer i's compute so XLA's
        # latency-hiding scheduler overlaps the int8 weight read +
        # dequant with matmuls it does not feed — the stage3_forward
        # FSDP-prefetch shape applied to the weight-streaming-bound
        # decode step
        ws = getattr(self, "_wstream_live", None)
        nxt_w = ws.dequant_layer(0) if ws is not None and ws.prefetch \
            else None
        for li in range(cfg.num_layers):
            if ws is None:
                cur_w = None
            elif ws.prefetch:
                cur_w = nxt_w
                nxt_w = ws.dequant_layer(li + 1) \
                    if li + 1 < cfg.num_layers else None
            else:
                # no-prefetch baseline: dequant issued AT use — no
                # overlap window (what measure_stream_win compares with)
                cur_w = ws.dequant_layer(li)
            with _scopes.scope("attention"):
                h = self.ln1[li](x)
                qkv = self._lin("qkv", li, h, cur_w)   # [T, (HQ+2HKV)*D]
                # stacked-cache mode: each layer reads/writes its slice
                # of the ONE [L, pool] cache pair (single
                # dynamic-update-slice chain — the list+jnp.stack pattern
                # rebuilt the full cache every step)
                outs = IF.block_multihead_attention(
                    qkv, new_kc, new_vc,
                    seq_lens_encoder, seq_lens_decoder,
                    seq_lens_this_time, None, None, cu_seqlens_q, None,
                    block_tables,
                    cache_k_quant_scales=new_ks if quant else None,
                    cache_v_quant_scales=new_vs if quant else None,
                    use_dynamic_cachekv_quant=quant,
                    rope_emb=rope, layer_idx=li,
                    max_seq_len=cfg.max_seq, block_size=cfg.block_size,
                    fresh_prefill=getattr(self, "_step_mode", None)
                    == "fresh_prefill",
                    last_row_is_padding=True)
                if quant:
                    out, _, new_kc, new_vc, new_ks, new_vs = outs
                else:
                    out, _, new_kc, new_vc = outs
                x = x + self._lin("proj", li, out, cur_w)
            with _scopes.scope("mlp"):
                h = self.ln2[li](x)
                x = x + self._mlp(li, h, cur_w)
        with _scopes.scope("head"):
            x = self.ln_f(x)
            if getattr(self, "_step_mode", None) == "spec_verify":
                # speculative verify: logits at EVERY packed position
                # (the engine samples each drafted slot with its own salt
                # and accepts the longest matching run), not pick_last
                logits = self.head(x)                    # [T, V]
            else:
                # last token of each row: cu_q[i+1]-1 (rows with 0 tokens
                # this step read their previous row's last token — masked
                # host-side)
                def pick_last(xa, cu):
                    idx = jnp.maximum(cu[1:] - 1, 0)
                    return xa[idx]

                last = apply(pick_last, x, cu_seqlens_q,
                             op_name="pick_last")
                logits = self.head(last)                 # [B+1, V]
        if quant:
            return logits, new_kc, new_vc, new_ks, new_vs
        return logits, new_kc, new_vc

    # -- stateless dense reference over the same weights -----------------
    def forward_dense(self, input_ids):
        """input_ids [1, S] -> logits [1, S, V] with standard causal GQA
        attention; numerically the reference for the paged path."""
        cfg = self.cfg
        ids = input_ids.reshape([-1])
        S = ids.shape[0]
        x = self.embed(ids)

        def attn_dense(qkva):
            T = qkva.shape[0]
            HQ, HKV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
            q = qkva[:, :HQ * D].reshape(T, HQ, D)
            k = qkva[:, HQ * D:(HQ + HKV) * D].reshape(T, HKV, D)
            v = qkva[:, (HQ + HKV) * D:].reshape(T, HKV, D)
            cos, sin = self._rope_table(jnp.arange(T))       # [T, D/2]
            cos_h = cos[:, None, :].astype(jnp.float32)
            sin_h = sin[:, None, :].astype(jnp.float32)

            def rope_t(t):
                td = t.astype(jnp.float32)
                t1, t2 = td[..., 0::2], td[..., 1::2]
                return jnp.stack([t1 * cos_h - t2 * sin_h,
                                  t2 * cos_h + t1 * sin_h],
                                 axis=-1).reshape(t.shape).astype(t.dtype)

            q, k = rope_t(q), rope_t(k)
            if HQ != HKV:
                rep = HQ // HKV
                k = jnp.repeat(k, rep, axis=1)
                v = jnp.repeat(v, rep, axis=1)
            logits = jnp.einsum("thd,shd->ths", q.astype(jnp.float32),
                                k.astype(jnp.float32)) \
                / jnp.sqrt(jnp.float32(D))
            causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
            logits = jnp.where(causal[:, None, :], logits, -jnp.inf)
            probs = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("ths,shd->thd", probs,
                             v.astype(jnp.float32)).astype(qkva.dtype)
            return out.reshape(T, HQ * D)

        for li in range(cfg.num_layers):
            h = self.ln1[li](x)
            qkv = self.qkv[li](h)
            out = apply(attn_dense, qkv, op_name="dense_ref_attn")
            x = x + self.proj[li](out)
            h = self.ln2[li](x)
            x = x + self._mlp(li, h)
        x = self.ln_f(x)
        return self.head(x).reshape([1, S, cfg.vocab_size])




class _Request:
    __slots__ = ("rid", "prompt", "generated", "max_new", "pages",
                 "cached", "done", "sampling", "eos_token_id",
                 "submit_t", "first_tok_t", "last_tok_t", "deadline_t",
                 "timed_out",
                 "shared_keys", "prefix_registered", "salt_rid",
                 "salt_seed", "trace", "sched_t0", "requeues", "tenant",
                 "spec_observed", "weight_version", "slot", "ahead",
                 "ahead_row")

    def __init__(self, rid, prompt, max_new, sampling, eos_token_id,
                 deadline_s=None, arrival_t=None):
        self.rid = rid
        self.prompt = list(int(t) for t in prompt)
        self.generated = []
        self.max_new = max_new
        self.pages = []
        self.cached = 0        # tokens whose KV currently lives in pages
        # the row slot of a model whose layers keep a fixed state a
        # request (layer_states.py); None until a step first schedules it
        self.slot = None
        # the engine's one unsettled step (ServingEngine.settle): how many
        # of this request's tokens it holds, and the row whose sampled
        # token it will give the request (-1: none). `cached` and
        # `generated` are the SETTLED ones; the scheduler adds these
        self.ahead = 0
        self.ahead_row = -1
        self.done = False
        self.sampling = sampling or GREEDY
        self.eos_token_id = eos_token_id
        now = time.perf_counter()
        # where the request's own clock starts: when it reached whoever
        # called add_request, if they say, else this call
        self.submit_t = now if arrival_t is None else float(arrival_t)
        self.first_tok_t = None
        self.last_tok_t = None     # when the newest token arrived
        self.deadline_t = None if deadline_s is None \
            else now + float(deadline_s)
        self.timed_out = False
        # prefix-cache bookkeeping: trie node keys this request holds a
        # ref on (leading shared pages), and whether its own full prompt
        # blocks were registered after prefill
        self.shared_keys = []
        self.prefix_registered = False
        # sampling-salt identity: a request migrated between engines
        # (disaggregated prefill/decode) keeps its ORIGIN (seed, rid) so
        # its token stream is bitwise-identical to the single-engine path
        self.salt_rid = rid
        self.salt_seed = None      # None = use the engine's seed
        # distributed-tracing identity: the admission span's context —
        # every later lifecycle span (queue/prefill/migrate/decode)
        # parents to it, and it travels in disagg/requeue hand-off
        # payloads so a migrated request's spans share one trace id
        self.trace = None
        self.sched_t0 = None       # first time a step scheduled this row
        # deadline-requeue accounting: how many times a router has
        # already retried this request on another replica — the bounded
        # cap lives in ReplicaRouter.max_requeues
        self.requeues = 0
        # admission tenant: prefix-cache namespace + the gateway's
        # fairness/quota identity; None = the shared default namespace
        self.tenant = None
        # speculative decoding: how much of prompt+generated the
        # engine's drafter has already observed (0 on any new engine —
        # a migrated/requeued request re-teaches the peer's drafter)
        self.spec_observed = 0
        # live weight publishing: the version this stream is PINNED to.
        # KV depends on params, so the whole stream runs under exactly
        # one version — pinned at admission, carried across requeue /
        # drain / migrate hand-offs, and a step only batches rows that
        # share one version (see _schedule)
        self.weight_version = 0

    @property
    def length(self):
        return len(self.prompt) + len(self.generated)


class _Flight:
    """The one step an engine has dispatched and not settled: its rows
    and their chunks, which rows reached their tip, the sampler's output
    and the model's step counts, both still on the device."""

    __slots__ = ("rows", "tip", "sampled", "counts")

    def __init__(self, rows, tip, sampled, counts):
        self.rows, self.tip = rows, tip
        self.sampled, self.counts = sampled, counts


class ServingEngine:
    """Continuous-batching scheduler over a PagedCausalLM step function.

    engine = ServingEngine(path_prefix, cfg)      # loads the artifact
    engine = ServingEngine.from_model(model, cfg) # or jit a live model
    rid = engine.add_request([tokens...], max_new_tokens=8,
                             sampling=SamplingParams(temperature=0.8,
                                                     top_k=50, top_p=0.9))
    engine.step()                # one mixed prefill/decode batch step
    engine.decode_run(16)        # 16 decode steps, ONE host sync
    engine.run_to_completion() -> {rid: [generated tokens]}
    Requests may be added between steps (continuous batching); prompts
    longer than the token budget prefill in chunks; finished requests
    release their cache pages.

    One step is kept in flight. `step()` call k schedules, packs and
    dispatches step k and only then fetches and emits step k-1, which ran
    on the chip meanwhile: it returns the tokens that became known in
    this call, those of the step the PREVIOUS call dispatched. A decode
    row of step k takes its input token from step k-1's sampler on the
    device. `pending()` stays true while a request has a token in
    flight, and a `step()` that can schedule nothing settles and returns,
    so `while engine.pending(): engine.step()` needs no change. Between
    calls a request's `generated`, `cached`, `done` and `pages` are the
    SETTLED ones; `settle()` fetches the step in flight at once, and
    everything that reads or moves a request's tokens, pages or state
    outside the plain step (`decode_run`, a drafter, pre-emption, a
    deadline eviction, `probe_logits`, a weight commit or rollback, a
    prefix-cache snapshot, a migration) settles first; the tokens that
    emits are held for the next `step()` or `decode_run()` to return
    first, so what those two return, taken together, is every stream
    whole. A stop by `eos_token_id` is seen one step late: the token the
    next step computed for that request is dropped, never emitted.
    """

    def __init__(self, path_prefix: str = None,
                 cfg: PagedServingConfig = None, device=None, seed=0,
                 layer_states=None):
        from .layer_states import LayerStates

        # what the model's layers keep for a request, by layer kind
        # (layer_states.py): pages for its attention layers and, for
        # state-space layers, a row slot. Default: attention, every layer
        states = layer_states or LayerStates.attention_only(cfg)
        self._states = states
        if states.row_states:
            # a fixed state a row is not addressed by position: nothing
            # that shares, rolls back or re-encodes positions applies
            if cfg.prefix_cache:
                raise ValueError(
                    "prefix_cache=True with a state-space layer: a shared "
                    "prefix's pages say nothing of the recurrent state at "
                    "its end; a prefix cache over row state is not built")
            if cfg.cache_quant is not None:
                raise ValueError(
                    "cache_quant='int8' with a state-space layer: the row "
                    "state is float32 by contract and the quantised pages' "
                    "gathered attention is not this model's path")
        if path_prefix is not None:
            from . import load_inference_model

            ex, params, buffers, sig = load_inference_model(path_prefix)
            # stage weights into HBM once — calls must not re-transfer
            self._params = jax.device_put(params)
            self._buffers = jax.device_put(buffers)
            self._compiled = jax.jit(
                lambda p, b, *ins: ex.call(p, b, *ins))
            # the exported module has a FIXED token length; jit-based
            # engines (from_model) may feed shorter decode batches
            self._fixed_token_len = cfg.token_budget
        else:
            self._fixed_token_len = None
        self._compiled_fresh = None   # set by from_model (jit engines)
        # {step program: {kernel: its trace took it}} for the two kernels
        # over the page stacks (paged_attention, kv_page_write), written
        # when from_model's programs are traced
        self._kernel_programs = {}
        self._compiled_verify = None  # all-positions logits (from_model)
        # the from_model weight_stream mode this engine's flat params
        # were built under — a weight publisher must replicate the SAME
        # cast/quantize/flatten pipeline for its arrays to slot in
        self._weight_stream_mode = None
        # speculative decoding (inference/speculative.py): attached via
        # set_drafter; while set, _step diverts pure decode-tip batches
        # through _spec_step (draft k, verify in one paged step)
        self._drafter = None
        self._spec_k = 0
        self._spec_shapes = set()     # verify tok_lens compiled so far
        self._spec_drafted_total = 0
        self._spec_accepted_total = 0
        self.seed = seed
        self.cfg = cfg
        # explicit placement (heterogeneous fleets): the device= arg
        # wins, else cfg.backend resolves; None keeps the ambient
        # default — exactly the pre-seam behavior
        self._device = device if device is not None \
            else resolve_backend_device(getattr(cfg, "backend", None))
        shape = (states.attention_layers, cfg.num_blocks, states.kv_heads,
                 cfg.block_size, states.head_dim)
        if cfg.cache_quant == "int8":
            cache_dt = jnp.int8
            self._ks = self._alloc(shape[:-1], jnp.float32)
            self._vs = self._alloc(shape[:-1], jnp.float32)
        else:
            cache_dt = jnp.bfloat16 if cfg.dtype == "bfloat16" \
                else jnp.float32
            self._ks = self._vs = None
        self._cache_dt = cache_dt
        self._kc = self._alloc(shape, cache_dt)
        self._vc = self._alloc(shape, cache_dt)
        # page 0 is the trash page for padding tokens
        self._free_pages = list(range(1, cfg.num_blocks))
        # row state: {name: [layers, max_batch + 1, *shape]}, one slot a
        # request; the last slot is the padding row's and is never free
        self._row_state = {
            rs.name: self._alloc((rs.layers, cfg.max_batch + 1) + rs.shape,
                                 jnp.dtype(rs.dtype))
            for rs in states.row_states}
        self._free_slots = list(range(cfg.max_batch)) \
            if states.row_states else []
        # page sides: {name: [layers, num_blocks, *shape]},
        # addressed by the block table like the pages, and like them
        # donated to the step and kept from its return
        self._page_side = {
            ps.name: self._alloc((ps.layers, cfg.num_blocks) + ps.shape,
                                 jnp.dtype(ps.dtype))
            for ps in states.page_sides}
        # the step's own counts (states.counters), still on the device
        # until the step's sampled tokens are fetched
        self._step_counts = None
        # the step dispatched and not yet settled (_Flight), or None
        self._flight = None
        # tokens a settle emitted for a caller with no stream to give
        # them to (settle(hold=True)): the next step(), decode_run() or
        # settle() returns them first
        self._held = []
        self._requests = {}
        self._next_rid = 0
        self._window_fns = {}
        # shared-prefix KV reuse (cfg.prefix_cache=True): refcounted trie
        # over the page pool; consulted at admission so a hit skips the
        # shared tokens' prefill entirely
        if cfg.prefix_cache:
            from .prefix_cache import PrefixCache

            self._prefix_cache = PrefixCache(
                cfg.block_size,
                page_quota=getattr(cfg, "prefix_page_quota", None))
        else:
            self._prefix_cache = None
        # deadline-evicted requests are surfaced here instead of dropped:
        # the replica router installs a hook that retries them on another
        # replica (hook receives the dict from _requeue_info; it must not
        # raise — a failing hook fails the engine step sweeping it)
        self.requeue_hook = None
        # liveness: a kill@prefill/decode/cache_save chaos fault (or the
        # fleet supervisor) fells THIS engine in-process — every call
        # into a dead engine raises EngineDeadError until it is replaced
        self.dead = False
        self.name = f"engine{seed}"
        # serving/* metric handles; set_metrics_namespace rebinds them to
        # a per-replica child registry (Replica does this at wrap time)
        self.metrics_namespace = None
        self._m = _EngineMetrics(_metrics.registry(), states.counters)
        # step programs already registered with profiler.scopes
        self._programs = {}     # (name, token length) -> scopes.Program
        # rank the chaos injector sees for this engine's fault sites, so
        # PT_FAULT_PLAN ":rank=R" clauses target one replica of a fleet
        self.fault_rank = 0
        # live weight publishing (inference/weight_publish.py):
        # _active_wv is the version NEW requests pin to; _weight_sets
        # retains the flat param list per still-referenced version (the
        # active one, the previous one for bitwise rollback, and any
        # older version an in-flight stream is still pinned to);
        # _staged_weights holds fully-verified-but-uncommitted sets —
        # the double buffer a commit swaps in at a step boundary
        self._active_wv = 0
        self._prev_wv = None
        self._weight_sets = {}
        self._staged_weights = {}
        from ..distributed.resilience import faults as _faults

        _faults.maybe_arm_from_env()
        if self._prefix_cache is not None \
                and getattr(cfg, "prefix_snapshot_root", None):
            from .prefix_cache import restore_snapshot

            restore_snapshot(self, cfg.prefix_snapshot_root)

    def _alloc(self, shape, dt):
        """KV-pool allocation on the engine's resolved device (ambient
        default when no backend handle was threaded through)."""
        if self._device is not None:
            with jax.default_device(self._device):
                return jnp.zeros(shape, dt)
        return jnp.zeros(shape, dt)

    @classmethod
    def from_model(cls, model, cfg: PagedServingConfig,
                   seed=0, weight_stream=None):
        """Build an engine directly over a live model (no disk artifact):
        the step function is jitted from the layer's functional form, with
        floating params cast to cfg.dtype (bf16 serving regime). The
        compiled step and staged weights are cached on the model, so
        several engines over the same model share one executable and one
        HBM weight copy (weights are snapshotted at the first call).

        ``weight_stream`` streams the decoder Linear stacks as
        per-channel int8 (inference/weight_stream.py), dequantized on use
        with the NEXT layer's group issued before the current layer's
        compute — double-buffered, so that the next layer's weight read
        can overlap matmuls it does not feed.
        ``"int8"`` prefetches; ``"int8-noprefetch"`` dequantizes at use
        (the baseline ``weight_stream.measure_stream_win`` compares the
        overlap with); ``"int4"`` packs two 4-bit codes per byte with
        per-(input-group, out-channel) scales — quarter the streamed
        bytes of bf16 at a larger quant error.  Generations match an
        engine over the dequantized weights bitwise; vs the
        full-precision engine they differ by the quantization error."""
        from ..jit import functional as FB

        if weight_stream not in (None, "int8", "int8-noprefetch",
                                 "int4"):
            raise ValueError(
                f"weight_stream={weight_stream!r}: expected None, "
                f"'int8', 'int8-noprefetch' or 'int4'")
        states = model.layer_states()
        if weight_stream is not None and states.row_states:
            raise ValueError(
                f"weight_stream={weight_stream!r} with a state-space "
                "layer: the streamer knows PagedCausalLM's Linear stacks, "
                "not this model's")
        eng = cls(None, cfg, seed=seed, layer_states=states)
        eng._weight_stream_mode = weight_stream
        # the backend handle joins the share key: engines on different
        # devices must not share one staged weight copy or executable
        share_key = (cfg.dtype, cfg.cache_quant, weight_stream,
                     str(getattr(cfg, "backend", None)))
        cached = getattr(model, "_serving_shared", None)
        if cached is not None and cached[0] == share_key:
            (_, eng._compiled, eng._compiled_fresh,
             eng._compiled_verify, eng._params, eng._buffers,
             eng._kernel_programs) = cached
            return eng
        functional = not isinstance(model, Layer)
        if functional:
            # a model of pure functions over its own parameters
            # (models/nemotron_h.py): made in the served dtype, taken as
            # they are; `serving_step(params, *ins, mode=)` is the step
            cast, buffers = model.serving_params(), {}
        else:
            params = FB.current_params(model)
            buffers = FB.current_buffers(model)
            tgt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
            cast = jax.tree_util.tree_map(
                lambda a: a.astype(tgt)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                else a, params)
        if weight_stream is not None:
            from .weight_stream import WeightStreamer

            streamer = WeightStreamer.build(
                model, cast, tgt,
                prefetch=weight_stream != "int8-noprefetch",
                mode="int4" if weight_stream == "int4" else "int8")
        else:
            streamer = None
        flat_p, tree_p = jax.tree_util.tree_flatten(cast)
        n_base = len(flat_p)
        if streamer is not None:
            flat_p = flat_p + streamer.flat()
        flat_b, tree_b = jax.tree_util.tree_flatten(buffers)

        kernel_programs = eng._kernel_programs

        def pure(fp, fb, *ins):
            from ..ops.pallas import kv_page_write, paged_attention

            ps = jax.tree_util.tree_unflatten(tree_p, fp[:n_base])
            bs = jax.tree_util.tree_unflatten(tree_b, fb)
            if streamer is not None:
                object.__setattr__(model, "_wstream_live",
                                   streamer.bind(fp[n_base:]))
            def traced():
                return {"paged_attention":
                        paged_attention.traced_kernel_calls(),
                        "kv_page_write": kv_page_write.traced_kernel_calls()}

            before = traced()
            try:
                if functional:
                    out = model.serving_step(
                        ps, *ins, mode=getattr(model, "_step_mode", None))
                else:
                    out, _ = FB.call_functional(model, ps, bs, ins,
                                                train=False)
            finally:
                if streamer is not None:
                    object.__setattr__(model, "_wstream_live", None)
            # this runs when a step program is traced: which of the two
            # kernels over the pages that trace took (_count_step reads it)
            kernel_programs[_STEP_PROGRAMS[
                getattr(model, "_step_mode", None)]] = {
                    k: n > before[k] for k, n in traced().items()}
            return tuple(out)

        def pure_fresh(fp, fb, *ins):
            # trace-time flag: every scheduled row starts at cache pos 0,
            # so attention is block-diagonal varlen flash over the packed
            # step (no page-pool gather)
            object.__setattr__(model, "_step_mode", "fresh_prefill")
            try:
                return pure(fp, fb, *ins)
            finally:
                object.__setattr__(model, "_step_mode", None)

        def pure_verify(fp, fb, *ins):
            # trace-time flag: the LM head runs at every packed position
            # (speculative verify samples each drafted slot)
            object.__setattr__(model, "_step_mode", "spec_verify")
            try:
                return pure(fp, fb, *ins)
            finally:
                object.__setattr__(model, "_step_mode", None)

        # the programs' names in a device trace's "XLA Modules" line
        pure.__name__ = "serving_step"
        pure_fresh.__name__ = "serving_fresh_prefill"
        pure_verify.__name__ = "serving_spec_verify"
        eng._params = jax.device_put(flat_p)
        eng._buffers = jax.device_put(flat_b)
        # every step program takes its pages, and the row state or the int8
        # scale pools after them, as donated arguments: the stacks are
        # updated where they lie, and a caller keeps what the step returns
        # (ins: tokens, enc, dec, this, cu, bt, kc, vc, then *row state,
        # *page sides, slots or ks, vs)
        donate = tuple(range(8, 10 + (
            len(states.row_states) + len(states.page_sides)
            or 2 * (cfg.cache_quant == "int8"))))
        eng._compiled = jax.jit(pure, donate_argnums=donate)
        eng._compiled_fresh = jax.jit(pure_fresh, donate_argnums=donate)
        eng._compiled_verify = None if functional \
            else jax.jit(pure_verify, donate_argnums=donate)
        object.__setattr__(model, "_serving_shared",
                           (share_key, eng._compiled,
                            eng._compiled_fresh, eng._compiled_verify,
                            eng._params, eng._buffers,
                            eng._kernel_programs))
        return eng

    # -- scheduling ------------------------------------------------------
    def add_request(self, prompt_tokens, max_new_tokens=8, sampling=None,
                    eos_token_id=None, deadline_s=None, tenant=None,
                    arrival_t=None):
        """Admit one request. `arrival_t` is the `time.perf_counter()`
        at which the request reached the caller (a gateway, a router, a
        load generator that knows when it was due): the request's
        `submit_t`, its `serving::queue` span and `serving/ttft_ms` then
        start there, not at this call, so time spent waiting to be
        admitted counts. `deadline_s` (seconds from this call) bounds
        its total latency: a request still unfinished past its deadline
        is evicted at the next step (pages released, `timed_out` set)
        so a stuck/starved request cannot pin pool pages forever.
        `tenant` scopes the request's prefix-cache reads/writes to that
        tenant's namespace (inference/prefix_cache.py): tenants never
        hit each other's cached prefixes and each is bounded by its
        page quota.  Raises EngineOverloadedError when cfg.max_queue
        live requests already exist (load shedding at admission, not
        deep in the queue)."""
        admit_t0 = time.perf_counter()
        self._check_alive()
        if len(prompt_tokens) == 0:
            raise ValueError("prompt must contain at least one token "
                             "(an empty row would read another request's "
                             "logits)")
        if len(prompt_tokens) + max_new_tokens > self.cfg.max_seq:
            raise ValueError("prompt + max_new_tokens exceeds max_seq")
        max_queue = self.cfg.max_queue
        if max_queue is not None and len(self.pending()) >= max_queue:
            # a request whose last token is in flight still counts as
            # live: shed on the settled count only
            self.settle(hold=True)
            if len(self.pending()) >= max_queue:
                self._m.shed.inc()
                raise EngineOverloadedError(
                    f"engine saturated: {len(self.pending())} live "
                    f"requests >= max_queue={max_queue}; shed this request "
                    f"(retry later or on another replica)")
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(rid, prompt_tokens, max_new_tokens,
                       sampling, eos_token_id, deadline_s=deadline_s,
                       arrival_t=arrival_t)
        req.tenant = tenant
        # pin the whole stream to the version serving at admission: KV
        # depends on params, so a mid-stream swap would mix versions —
        # pinned streams drain under their version instead
        req.weight_version = self._active_wv
        self._requests[rid] = req
        self._try_prefix_match(req)
        # root (or ambient-parented) span of this request's trace; the
        # request adopts its context so every later lifecycle span links
        req.trace = _tracing.record_span(
            "serving::admit", admit_t0, time.perf_counter(),
            args={"rid": rid, "engine": self.name})
        self._m.requests.inc()
        return rid

    def set_metrics_namespace(self, namespace):
        """Bind this engine's serving/* writes to the named child
        registry of the global one (per-replica series that roll up),
        or back to the global registry when `namespace` is None."""
        self.metrics_namespace = namespace
        reg = _metrics.registry() if namespace is None \
            else _metrics.child(namespace)
        self._m = _EngineMetrics(reg, self._states.counters)
        return self._m

    def set_drafter(self, drafter, k=None):
        """Attach a speculative drafter (inference/speculative.py).

        While a drafter is set, any step whose scheduled batch is pure
        decode-tip rows runs as ONE speculative verify step: the
        drafter proposes up to ``k`` tokens per row, the target model
        scores the proposal in a single paged-attention dispatch, and
        each position is sampled under the SAME salt the plain path
        would use — so the emitted stream is token-bitwise-identical to
        non-speculative decoding, and rejected-tail KV pages roll back
        to the pool.  ``k`` defaults to ``PT_SPEC_K`` (env) or 4.
        ``set_drafter(None)`` turns speculation off."""
        if drafter is not None and self._row_state:
            raise ValueError(
                "speculative decoding with a state-space layer: a rejected "
                "draft is rolled back by cached length, and a recurrent "
                "state that has consumed it cannot be; verify with state "
                "rollback is not built")
        if drafter is not None and self._compiled_verify is None:
            raise ValueError(
                "speculative decoding needs a from_model engine: the "
                "exported serving artifact has no all-positions verify "
                "entry")
        # a drafter reads a request's settled tokens: while one is set
        # every step settles before it returns
        self.settle(hold=True)
        self._drafter = drafter
        if k is not None:
            self._spec_k = int(k)
        elif self._spec_k <= 0:
            import os

            self._spec_k = int(os.environ.get("PT_SPEC_K", "4"))
        if self._spec_k < 1:
            raise ValueError("speculative draft length k must be >= 1")
        return drafter

    def _spec_observe(self, r):
        """Feed the drafter everything of this request it has not seen
        (prompt on first contact, then each newly emitted suffix)."""
        seq = r.prompt + r.generated
        if r.spec_observed < len(seq):
            self._drafter.observe(seq, start=r.spec_observed)
            r.spec_observed = len(seq)

    def _try_prefix_match(self, req):
        """Map the request's leading full prompt blocks onto cached pages
        (shared-prefix KV reuse): a hit sets ``cached`` past the shared
        tokens so scheduling skips their prefill entirely."""
        cache = self._prefix_cache
        if cache is None or req.pages:
            return
        pages, keys, n_tok = cache.match(req.prompt,
                                         namespace=req.tenant,
                                         version=req.weight_version)
        if n_tok:
            req.pages = list(pages)
            req.shared_keys = keys
            req.cached = n_tok
            self._m.prefix_pages.inc(len(pages))
        self._m.prefix_rate.set(cache.hit_rate())

    def _maybe_register_prefix(self, req, cached=None):
        """After a request's prompt is fully prefilled (`cached`, default
        the settled `req.cached`, covers it), publish its full prompt
        blocks into the prefix cache (ownership of those pages transfers
        to the cache; the request keeps a ref). A step registers what it
        has DISPATCHED: whoever matches those pages reads them in a later
        step, which the chip runs after this one."""
        cache = self._prefix_cache
        if cache is None or req.prefix_registered \
                or (req.cached if cached is None else cached) \
                < len(req.prompt):
            return
        req.prefix_registered = True
        req.shared_keys.extend(cache.insert(req.prompt, req.pages,
                                            namespace=req.tenant,
                                            version=req.weight_version))

    def _evict_expired(self):
        """Deadline sweep, run before scheduling: requests past their
        per-request deadline finish NOW as timed out — their pages go
        back to the pool instead of starving live traffic.  Each evicted
        request is surfaced through ``requeue_hook`` (when installed) so
        a replica router can retry it elsewhere instead of dropping it
        on the floor.  Returns the tokens it had to settle first."""
        now = time.perf_counter()
        expired = [r for r in self.pending()
                   if r.deadline_t is not None and now > r.deadline_t]
        # a request with a token in flight is evicted with that token
        # settled (it may be its last): what the router retries is the
        # settled stream
        settled = self._settle_forced() \
            if any(r.ahead for r in expired) else []
        for r in expired:
            if not r.done:
                r.timed_out = True
                r.done = True
                self._release(r)
                self._m.deadline.inc()
                if self.requeue_hook is not None:
                    self.requeue_hook(self._requeue_info(r))
        return settled

    @staticmethod
    def _requeue_info(r):
        """What a router needs to retry an evicted request on another
        replica: the full prompt (the new replica re-prefills — or
        prefix-cache-hits — it), progress so far, and the original
        budget/sampling."""
        return {"rid": r.rid, "prompt": list(r.prompt),
                "generated": list(r.generated), "max_new": r.max_new,
                "sampling": r.sampling, "eos_token_id": r.eos_token_id,
                "timed_out": True, "requeues": r.requeues,
                "tenant": r.tenant, "salt_rid": r.salt_rid,
                "salt_seed": r.salt_seed,
                "weight_version": r.weight_version,
                "trace": r.trace.to_dict() if r.trace is not None
                else None}

    def timed_out_requests(self):
        """rids evicted by the deadline sweep (serving front-end: 504)."""
        return [r.rid for r in self._requests.values() if r.timed_out]

    # -- liveness + chaos sites ------------------------------------------
    def _check_alive(self):
        # getattr: argument validation must stay usable on bare engines
        # built without __init__ (the empty-prompt contract test)
        if getattr(self, "dead", False):
            from ..distributed.resilience.errors import EngineDeadError

            raise EngineDeadError(self.name)

    def _fault_event(self, site):
        """Consult the chaos injector at a serving site.  ``kill`` fells
        THIS engine (dead flag + EngineDeadError — the in-process analog
        of the replica process dying); ``delay`` sleeps; frame-level
        kinds are meaningless here and ignored."""
        from ..distributed.resilience import faults as _faults

        act = _faults.injector.on_event(site, self.fault_rank)
        if act is None:
            return
        if act.kind == "kill":
            self.dead = True
            # the settled fields are the migratable state: the token in
            # flight is not emitted, and is sampled again under the same
            # salt where the request lands
            self._drop_flight()
            from ..distributed.resilience.errors import EngineDeadError

            raise EngineDeadError(self.name, site)
        if act.kind == "delay":
            time.sleep(act.delay_ms / 1e3)

    # -- prefix-cache persistence ----------------------------------------
    def save_prefix_cache(self, root=None, keep=None):
        """Snapshot the prefix cache (trie + owned KV pages) under
        `root` (default cfg.prefix_snapshot_root) via the atomic
        manifest pattern; returns the snapshot path or None (empty)."""
        from .prefix_cache import save_snapshot

        root = root or self.cfg.prefix_snapshot_root
        if root is None:
            raise ValueError("no snapshot root: pass root= or set "
                             "cfg.prefix_snapshot_root")
        self.settle(hold=True)
        return save_snapshot(self, root, keep=keep)

    def restore_prefix_cache(self, root=None):
        """Restore the newest complete snapshot under `root` (default
        cfg.prefix_snapshot_root) into this engine's cache; sweeps torn
        snapshot dirs first.  Returns blocks restored."""
        from .prefix_cache import restore_snapshot

        root = root or self.cfg.prefix_snapshot_root
        if root is None:
            raise ValueError("no snapshot root: pass root= or set "
                             "cfg.prefix_snapshot_root")
        return restore_snapshot(self, root)

    # -- live weight publishing (double-buffered versioned hot swap) -----
    @property
    def active_weight_version(self):
        """The version NEW admissions pin to (0 = the build-time set)."""
        return self._active_wv

    def has_weight_version(self, version):
        """True when `version` is SERVABLE here: active, or retained in
        the double buffer (an in-flight pinned stream can run under it).
        Staged-but-uncommitted sets do not count — they serve nothing."""
        return version == self._active_wv or version in self._weight_sets

    def _params_for(self, version):
        """Flat param list for a pinned version. Every dispatch site
        routes through this instead of touching ``_params`` directly, so
        a step binds exactly the version its rows are pinned to."""
        if version == self._active_wv:
            return self._params
        try:
            return self._weight_sets[version]
        except KeyError:
            raise KeyError(
                f"weight version {version} is not resident on engine "
                f"{self.name} (active={self._active_wv}, retained="
                f"{sorted(self._weight_sets)})") from None

    def pin_weight_version(self, rid, version):
        """Re-pin a just-admitted request to the version its stream
        STARTED under (the requeue / drain / migrate hand-off path:
        admission pinned it to this engine's active version, but the
        stream's KV-and-sampling identity belongs to its origin
        version).  Any prefix match taken under the admission version
        is released and re-taken under the pin — a pinned stream must
        never attend over another version's KV.  Raises KeyError when
        `version` is not servable here (callers skip this replica)."""
        r = self._requests[rid]
        if version == r.weight_version:
            return r
        if not self.has_weight_version(version):
            raise KeyError(
                f"engine {self.name} cannot serve weight version "
                f"{version} (active={self._active_wv})")
        if r.ahead:
            self.settle(hold=True)
        self._release(r)
        r.cached = 0
        r.prefix_registered = False
        r.weight_version = version
        self._try_prefix_match(r)
        return r

    def stage_weight_set(self, version, arrays, crcs=None):
        """Stage version `version` into the double buffer WITHOUT
        serving it: validate the tensor count/shapes/dtypes against the
        live flat param list, verify per-tensor CRCs when given (end-to-
        end integrity on top of the transport's frame CRCs), and
        device_put the set. The ``publish`` chaos site is consulted
        between receiving the bytes and installing the staged entry —
        manifest-last, so a ``kill@publish`` here leaves the engine dead
        with version N fully intact and nothing half-staged, a ``drop``
        makes the transfer vanish (the replica catches up later) and a
        ``corrupt`` flips a staged byte the CRC check must catch.
        Raises WeightTransferError on any integrity failure (the staged
        buffer is discarded; the engine keeps serving its version)."""
        from ..distributed.resilience.errors import WeightTransferError

        self._check_alive()
        cur = self._params
        host = [np.asarray(a) for a in arrays]
        if len(host) != len(cur):
            raise WeightTransferError(
                version, self.name,
                f"tensor count {len(host)} != expected {len(cur)}")
        for i, a in enumerate(host):
            ref = cur[i]
            if tuple(a.shape) != tuple(ref.shape) \
                    or a.dtype != ref.dtype:
                raise WeightTransferError(
                    version, self.name,
                    f"tensor {i}: got {a.dtype}{tuple(a.shape)}, "
                    f"expected {ref.dtype}{tuple(ref.shape)}")
        from ..distributed.resilience import faults as _faults
        from ..distributed.resilience.errors import (EngineDeadError,
                                                     PeerUnreachableError)

        act = _faults.injector.on_event("publish", self.fault_rank)
        if act is not None:
            if act.kind == "kill":
                self.dead = True
                raise EngineDeadError(self.name, "publish")
            if act.kind == "delay":
                time.sleep(act.delay_ms / 1e3)
            elif act.kind == "drop":
                raise PeerUnreachableError(self.fault_rank, self.name, 1)
            elif act.kind == "corrupt":
                big = max(range(len(host)),
                          key=lambda i: host[i].nbytes)
                buf = bytearray(host[big].tobytes())
                buf[len(buf) // 2] ^= 0xFF
                host[big] = np.frombuffer(
                    bytes(buf), host[big].dtype).reshape(host[big].shape)
        if crcs is not None:
            import zlib

            if len(crcs) != len(host):
                raise WeightTransferError(
                    version, self.name,
                    f"crc count {len(crcs)} != tensor count {len(host)}")
            for i, a in enumerate(host):
                got = zlib.crc32(a.tobytes()) & 0xFFFFFFFF
                if got != (crcs[i] & 0xFFFFFFFF):
                    raise WeightTransferError(
                        version, self.name,
                        f"tensor {i} CRC mismatch (got {got:#010x}, "
                        f"manifest {crcs[i] & 0xFFFFFFFF:#010x})")
        self._staged_weights[version] = jax.device_put(host)
        return version

    def commit_weight_set(self, version):
        """Atomically swap a STAGED version in at a step boundary: the
        current flat list is retained (bitwise rollback buffer + the
        params in-flight pinned streams keep draining under) and
        ``version`` becomes what new admissions pin to. Rebinding the
        flat list costs no retrace — shapes/dtypes are identical across
        versions, and the compiled step takes the params as an
        argument. Raises PublishRejectedError when `version` was never
        staged or does not advance the active version (stale publish)."""
        from ..distributed.resilience.errors import PublishRejectedError

        self._check_alive()
        self.settle(hold=True)
        if version <= self._active_wv:
            raise PublishRejectedError(
                "stale_version", version, fence_version=self._active_wv)
        staged = self._staged_weights.pop(version, None)
        if staged is None:
            raise PublishRejectedError(
                "not_staged", version,
                detail=f"stage_weight_set({version}, ...) never "
                       f"completed on engine {self.name}")
        old = self._active_wv
        self._weight_sets[old] = self._params
        self._weight_sets[version] = staged
        self._params = staged
        self._prev_wv = old
        self._active_wv = version
        self._gc_weight_sets()
        self._m.weight_swaps.inc()
        self._m.weight_version.set(version)
        return old

    def discard_staged(self, version=None):
        """Drop staged-but-uncommitted buffers (all, or one version) —
        the canary-rejection path: a refused candidate must not linger
        in device memory."""
        if version is None:
            self._staged_weights.clear()
        else:
            self._staged_weights.pop(version, None)

    def rollback_weight_set(self):
        """Roll back to the retained previous version, bitwise-equal to
        never having promoted: the previous flat list (retained at
        commit, never copied or rebuilt) becomes active again, and any
        in-flight stream pinned to the dropped version is RESET — pages
        released, generated tokens discarded — and re-pinned, so its
        re-generation under the schedule-independent salts reproduces
        exactly the stream a never-promoted engine would have emitted.
        Returns the version rolled back to."""
        from ..distributed.resilience.errors import PublishRejectedError

        self._check_alive()
        self.settle(hold=True)
        if self._prev_wv is None or self._prev_wv not in self._weight_sets:
            raise PublishRejectedError(
                "no_previous", self._active_wv,
                detail="nothing retained to roll back to")
        bad, prev = self._active_wv, self._prev_wv
        self._params = self._weight_sets[prev]
        self._active_wv = prev
        self._prev_wv = None          # a rollback cannot be rolled back
        for r in self.pending():
            if r.weight_version == bad:
                self._release(r)
                r.generated = []
                r.cached = 0
                r.prefix_registered = False
                r.spec_observed = 0
                r.weight_version = prev
                self._try_prefix_match(r)
        self._weight_sets.pop(bad, None)
        self._staged_weights.pop(bad, None)
        self._m.weight_rollbacks.inc()
        self._m.weight_version.set(prev)
        return prev

    def _gc_weight_sets(self):
        """Free retained flat lists no stream can reach: keep the
        active version, the rollback buffer, and every version an
        in-flight stream is still pinned to."""
        keep = {self._active_wv}
        if self._prev_wv is not None:
            keep.add(self._prev_wv)
        keep.update(r.weight_version for r in self.pending())
        for v in [v for v in self._weight_sets if v not in keep]:
            del self._weight_sets[v]

    def probe_logits(self, prompt, version=None):
        """Stateless canary probe: next-token logits of `prompt`'s last
        position under `version` (default: active), WITHOUT touching
        the KV pool's live pages, the scheduler, or any request state —
        the packed row runs through the fresh-prefill executable against
        the trash page. The probe can score
        a STAGED version before it is committed anywhere, which is how
        a poisoned candidate is rejected without ever serving a token.
        Returns a float32 vector of vocab logits."""
        self._check_alive()
        if self._compiled_fresh is None:
            raise ValueError(
                "probe_logits needs a from_model engine: the exported "
                "serving artifact has no fresh-prefill entry")
        cfg = self.cfg
        n = len(prompt)
        if not 0 < n <= cfg.token_budget:
            raise ValueError(
                f"probe prompt length {n} must be in [1, "
                f"{cfg.token_budget}] (one fresh-prefill shot)")
        self.settle(hold=True)
        wv = self._active_wv if version is None else version
        if wv == self._active_wv:
            fp = self._params
        elif wv in self._staged_weights:
            fp = self._staged_weights[wv]
        else:
            fp = self._params_for(wv)
        B1 = cfg.max_batch + 1
        enc = np.zeros(B1, np.int32)
        dec = np.zeros(B1, np.int32)
        this = np.zeros(B1, np.int32)
        this[0] = n
        n_pad = cfg.token_budget - n
        this[B1 - 1] = n_pad
        enc[B1 - 1] = n_pad
        tokens = np.asarray(list(prompt) + [0] * n_pad, np.int32)
        cu = np.zeros(B1 + 1, np.int32)
        cu[1:] = np.cumsum(this)
        bt = np.zeros((B1, cfg.max_blocks_per_seq), np.int32)
        # the probe's row writes the trash page (and the padding slot of a
        # row state); the step was given (donated) the engine's state, so
        # what it returns is kept
        logits = self._run_step(
            "serving_fresh_prefill", self._compiled_fresh, fp, tokens, enc,
            dec, this, cu, bt, np.full(B1, cfg.max_batch, np.int32))
        return np.asarray(logits, np.float32)[0]

    def _salt(self, r, n_generated):
        """Sampling salt under the request's ORIGIN identity: a request
        migrated from a prefill engine keeps its original (seed, rid) so
        disaggregated decode draws the single-engine path's randomness."""
        seed = self.seed if r.salt_seed is None else r.salt_seed
        return sampling_salt(seed, r.salt_rid, n_generated)

    def _note_first_token(self, req, now):
        req.last_tok_t = now
        if req.first_tok_t is None:
            req.first_tok_t = now
            self._m.ttft.observe((now - req.submit_t) * 1e3)
            if req.trace is not None:
                begin = req.sched_t0 if req.sched_t0 is not None \
                    else req.submit_t
                _tracing.record_span(
                    "serving::prefill", begin, now, parent=req.trace,
                    args={"rid": req.rid, "engine": self.name})

    def _trace_done(self, req, now):
        """Close the request's decode span (first token -> completion)."""
        if req.trace is None:
            return
        begin = req.first_tok_t if req.first_tok_t is not None \
            else req.submit_t
        _tracing.record_span(
            "serving::decode", begin, now, parent=req.trace,
            args={"rid": req.rid, "engine": self.name,
                  "tokens": len(req.generated)})

    def _update_pool_gauges(self, n_rows):
        cfg = self.cfg
        self._m.occupancy.set(n_rows / max(cfg.max_batch, 1))
        live = cfg.num_blocks - 1 - len(self._free_pages)  # page 0 = trash
        self._m.kv_util.set(live / max(cfg.num_blocks - 1, 1))

    def _take_free_page(self):
        """Pop one free page, reclaiming zero-ref prefix-cache pages
        under pool pressure (cache residency never blocks live traffic)."""
        if not self._free_pages and self._prefix_cache is not None:
            self._free_pages.extend(self._prefix_cache.evict(1))
        if not self._free_pages:
            raise RuntimeError("KV page pool exhausted")
        return self._free_pages.pop()

    def _ensure_pages(self, req, upto_len):
        need = math.ceil(upto_len / self.cfg.block_size)
        while len(req.pages) < need:
            req.pages.append(self._take_free_page())

    def _release(self, req):
        cache = self._prefix_cache
        if req.shared_keys:
            cache.release(req.shared_keys)
            req.shared_keys = []
        if cache is not None:
            owned = cache.owned_pages()
            self._free_pages.extend(p for p in req.pages
                                    if p not in owned)
        else:
            self._free_pages.extend(req.pages)
        req.pages = []
        if req.slot is not None:
            self._free_slots.append(req.slot)
            req.slot = None

    def _set_caches(self, kc, vc):
        # a bf16 artifact casts float outputs to f32 (the deploy-artifact
        # contract) — restore the cache dtype so the next call's input
        # avals match the exported signature
        if kc.dtype != self._cache_dt:
            kc, vc = kc.astype(self._cache_dt), vc.astype(self._cache_dt)
        self._kc, self._vc = kc, vc

    def pending(self):
        return [r for r in self._requests.values() if not r.done]

    def _schedule(self):
        """Pick <= max_batch rows and a prefill/decode chunk size for
        each within the token budget (vLLM-style chunked prefill: a
        request needing more tokens than fit this step takes the next
        chunk of its prompt+generated sequence). A row starts where the
        step in flight leaves it: its settled `cached` plus that step's
        chunk, its sequence one longer by the token that step samples;
        a row whose in-flight token is its `max_new`-th is done but for
        the fetch and is not scheduled again."""
        cfg = self.cfg
        rows = []
        budget = cfg.token_budget
        avail = len(self._free_pages)
        if self._prefix_cache is not None:
            # zero-ref cache pages are reclaimable on demand
            avail += self._prefix_cache.evictable_count()
        # one weight version per step: every scheduled row must share
        # the version the dispatch will bind, so after a hot swap the
        # step serves the OLDEST pending stream's version first (pre-
        # publish streams drain under N while new admissions wait one
        # scheduling round under N+1)
        step_wv = None
        # row state: a request without a slot needs a free one
        slots = len(self._free_slots) if self._row_state else None
        for r in self.pending():
            if len(rows) == cfg.max_batch or budget == 0:
                break
            if step_wv is not None and r.weight_version != step_wv:
                continue
            ahead_tok = r.ahead_row >= 0
            if ahead_tok and len(r.generated) + 1 >= r.max_new:
                continue
            cached = r.cached + r.ahead
            chunk = min(r.length + ahead_tok - cached, budget)
            cap = (len(r.pages) + avail) * cfg.block_size  # page-limited
            chunk = min(chunk, cap - cached)
            if chunk <= 0:
                continue  # defer: rerun once budget/pages free up
            if slots is not None and r.slot is None:
                if slots == 0:
                    continue
                slots -= 1
            pages_needed = max(
                math.ceil((cached + chunk) / cfg.block_size)
                - len(r.pages), 0)
            budget -= chunk
            avail -= pages_needed
            rows.append((r, chunk))
            step_wv = r.weight_version
        return rows

    def step(self):
        """One engine iteration: schedule <= max_batch live requests
        (prefill chunks + decode mixed) within the token budget, dispatch
        the step function and the sampler for the rows that reached their
        sequence tip, THEN fetch and emit the step the previous call
        dispatched, which ran on the chip meanwhile.

        Returns the (rid, token) pairs that became known since the last
        call: those of the step in flight when it was called, of anything
        it had to settle before it could schedule, and first those a
        call between the two settled and held (a weight commit, an
        admission at `max_queue`, a migration: `settle(hold=True)`), so
        that what `step()` and `decode_run()` return, taken together, is
        every request's whole stream. The step it dispatches stays in
        flight until the next call, or `settle()`. When nothing can be
        scheduled it settles and returns, so a loop
        `while engine.pending(): engine.step()` drains the engine.

        The step's own account of itself: one `serving::step` span (ring,
        flight recorder, and a `TraceAnnotation` on the device trace's
        clock) with what it held in its args (`lookahead` 1 where it was
        dispatched with the step before it unsettled), and a child for
        each phase: `serving::schedule`, `serving::pack`,
        `serving::sample_sync` (this step's sampler dispatched, then the
        wait for the PREVIOUS step's tokens, with this one enqueued
        behind it), `serving::emit`."""
        with _tracing.span("serving::step") as sp:
            produced = self._step(sp.args)
        return self._take_held() + produced

    def _take_held(self):
        held, self._held = self._held, []
        return held

    def settle(self, hold=False):
        """Fetch and emit the step in flight, if any: afterwards every
        request's `generated`, `cached`, `done` and `pages` say all the
        engine has computed. Whoever reads or moves a request's tokens,
        pages or state from outside `step()` calls this first. Returns
        the (rid, token) pairs emitted and not yet returned by any call
        (`[]` with nothing in flight: calling it again changes nothing);
        they are the caller's to stream. A caller with no stream to give
        them to passes `hold=True`: the pairs stay with the engine, the
        next `step()`, `decode_run()` or `settle()` returns them first,
        and this call returns `[]`. A dead engine's step in flight is
        dropped instead: its requests keep their settled state, which is
        what migrates."""
        self._held += self._settle_forced()
        return [] if hold else self._take_held()

    def _settle_forced(self):
        """A settle that something other than the next step asked for:
        the pairs the step in flight emits (a dead engine's is dropped)."""
        if self._flight is None:
            return []
        if self.dead:
            self._drop_flight()
            return []
        self._m.settle_forced.inc()
        return self._settle()

    def _settle(self):
        """The wait for the step in flight and its book-keeping, under
        the spans a step gives them."""
        with _tracing.phase("serving::sample_sync"):
            flight = self._fetch_flight()
        with _tracing.phase("serving::emit"):
            return self._emit_flight(flight)

    def _fetch_flight(self):
        """Take the step in flight off the engine and wait for what it
        sampled: the step, its tokens and model counts now on the host."""
        flight, self._flight = self._flight, None
        if flight is not None and flight.sampled is not None:
            flight.sampled = np.asarray(flight.sampled)
            if flight.counts is not None:
                # the chip has finished the step: this fetch waits for
                # nothing more
                flight.counts = np.asarray(flight.counts)
        return flight

    def _emit_flight(self, flight):
        """A fetched step's book-keeping: its rows advance, its tokens are
        appended, finished requests give their pages back."""
        produced = []
        if flight is None:
            return produced
        if flight.counts is not None:
            for c, n in zip(self._m.step_counts, flight.counts):
                c.inc(int(n))
        now = time.perf_counter()
        for i, (r, chunk) in enumerate(flight.rows):
            r.ahead, r.ahead_row = 0, -1
            if r.done:
                # finished, evicted or released since the step was
                # dispatched (an end-of-stream token is seen one step
                # late): what the step computed for it is dropped
                continue
            r.cached += chunk
            if not flight.tip[i]:
                continue
            nxt = int(flight.sampled[i])
            r.generated.append(nxt)
            produced.append((r.rid, nxt))
            self._note_first_token(r, now)
            if len(r.generated) >= r.max_new \
                    or (r.eos_token_id is not None
                        and nxt == r.eos_token_id):
                self._finish(r, now)
        self._m.tokens.inc(len(produced))
        return produced

    def _drop_flight(self):
        """Forget the step in flight without emitting it."""
        flight, self._flight = self._flight, None
        if flight is not None:
            for r, _ in flight.rows:
                r.ahead, r.ahead_row = 0, -1

    def _count_step(self, note, program, rows, tokens, pad,
                    prefill_tokens):
        """What one step held: the `serving/step_*` counters, each bumped
        once a step, and the same numbers as the step span's args; and
        which of the kernels over the pages `program`, which it ran, holds
        (known once the program has been traced: call this after it)."""
        m = self._m
        self._count_kernel_steps(program, 1)
        m.step_rows.inc(rows)
        m.step_tokens.inc(tokens)
        m.step_pad.inc(pad)
        m.step_prefill.inc(prefill_tokens)
        note.update(rows=rows, tokens=tokens, pad=pad,
                    prefill_tokens=prefill_tokens)

    def _count_kernel_steps(self, program, n):
        held = self._kernel_programs.get(program, {})
        if held.get("paged_attention"):
            self._m.paged_steps.inc(n)
        if held.get("kv_page_write"):
            self._m.inplace_steps.inc(n)

    def _finish(self, r, now):
        """A request's last token has arrived: pages back, the decode span
        closed, and its time per output token observed."""
        r.done = True
        self._release(r)
        self._trace_done(r, now)
        if len(r.generated) > 1 and r.first_tok_t is not None:
            self._m.tpot.observe((r.last_tok_t - r.first_tok_t)
                                 / (len(r.generated) - 1) * 1e3)

    def _step(self, note):
        cfg = self.cfg

        with _tracing.phase("serving::schedule"):
            self._check_alive()
            settled = self._evict_expired()
            rows = self._schedule()
            # nothing can go ahead of the tokens in flight (each row's
            # last token is among them, or the pool is too tight to grow
            # any): settle, and let the next call schedule, and pre-empt
            # if it must, from settled state
            settle_only = not rows and self._flight is not None
            preempted = set()
            while not rows and not settle_only and self.pending():
                # pool deadlock: in-flight requests hold pages but none
                # can grow — preempt the NEWEST holder (FCFS priority: the
                # oldest request always makes progress, so symmetric
                # requests cannot thrash each other's pages), vLLM-style.
                # The victim releases its pages and re-prefills
                # prompt+generated in chunks later.
                holders = [r for r in self.pending() if r.pages]
                if not holders:
                    raise RuntimeError(
                        "KV page pool exhausted: no pending request fits "
                        f"in {len(self._free_pages)} free pages — raise "
                        "num_blocks or lower concurrency")
                victim = max(holders, key=lambda r: r.rid)
                self._release(victim)
                victim.cached = 0
                victim.prefix_registered = False
                if victim.rid not in preempted:
                    # its shared prefix may still be resident: re-match so
                    # the re-prefill only covers tokens past the cached
                    # blocks — but only ONCE per sweep (a re-acquired
                    # prefix makes the victim a page holder again;
                    # re-matching it every pass would spin this loop
                    # forever)
                    self._try_prefix_match(victim)
                preempted.add(victim.rid)
                self._m.preempt.inc()
                rows = self._schedule()
        if settle_only:
            settled += self._settle()
            if self.pending():
                # work remains that could not go ahead of these tokens
                self._m.settle_forced.inc()
            return settled
        if not rows:
            return settled
        # where each row starts: behind what the step in flight holds
        starts = [r.cached + r.ahead for r, _ in rows]
        # chaos sites, consulted BEFORE any page allocation or cache
        # mutation: a kill here leaves every scheduled request in a
        # consistent pre-step state (decode rows still at their tip), so
        # the fleet supervisor can migrate them losslessly
        if any(c < len(r.prompt) for (r, _), c in zip(rows, starts)):
            self._fault_event("prefill")
        if any(c >= len(r.prompt) for (r, _), c in zip(rows, starts)):
            self._fault_event("decode")
        self._m.steps.inc()
        # first scheduling of a request ends its queue span
        now_sched = time.perf_counter()
        for r, _chunk in rows:
            if r.sched_t0 is None:
                r.sched_t0 = now_sched
                if r.trace is not None:
                    _tracing.record_span(
                        "serving::queue", r.submit_t, now_sched,
                        parent=r.trace,
                        args={"rid": r.rid, "engine": self.name})

        # speculative divert: a pure decode-tip batch (every scheduled
        # row needs exactly its next token) runs as one draft+verify
        # step instead — transparent to every caller of step(), so the
        # router/gateway/supervisor tiers become speculative unchanged
        # (with a drafter set nothing is in flight here: see below)
        if self._drafter is not None and all(
                chunk == 1 and r.cached == r.length - 1
                for r, chunk in rows):
            return settled + self._spec_step(rows, note)

        flight = self._flight
        with _tracing.phase("serving::pack"):
            B1 = cfg.max_batch + 1
            enc = np.zeros(B1, np.int32)
            dec = np.zeros(B1, np.int32)
            this = np.zeros(B1, np.int32)
            bt = np.zeros((B1, cfg.max_blocks_per_seq), np.int32)  # 0=trash
            packed = []
            prefill_tokens = 0
            # each row's slot; rows the step does not hold, and the
            # padding row, point at the padding slot
            slots = np.full(B1, cfg.max_batch, np.int32)
            # for a token still on the device (the one the step in flight
            # samples for this row): that step's row, else -1
            src = None
            for i, (r, chunk) in enumerate(rows):
                cached = starts[i]
                dec[i] = cached                  # chunk starts at this pos
                this[i] = chunk
                if cached < len(r.prompt):
                    prefill_tokens += chunk
                self._ensure_pages(r, cached + chunk)
                bt[i, :len(r.pages)] = r.pages
                past = cached - len(r.prompt)
                if past >= 0:       # a decode row: no copy of its history
                    toks = r.generated[past:past + chunk]
                else:
                    toks = (r.prompt + r.generated)[cached:cached + chunk]
                if len(toks) < chunk:
                    # the chunk ends on the token in flight
                    if src is None:
                        src = np.full(cfg.token_budget, -1, np.int32)
                    src[len(packed) + len(toks)] = r.ahead_row
                    toks = toks + [0]
                packed.extend(toks)
                if self._row_state:
                    if r.slot is None:
                        r.slot = self._free_slots.pop()
                    slots[i] = r.slot
            self._update_pool_gauges(len(rows))
            # padding tokens -> trash row (index B1-1, block table all
            # page 0)
            n_pad = cfg.token_budget - len(packed)
            this[B1 - 1] = n_pad
            enc[B1 - 1] = n_pad
            tokens = np.asarray(packed + [0] * n_pad, np.int32)
            if src is not None:
                tokens = _feed_tokens_dev(flight.sampled, src, tokens)
            cu = np.zeros(B1 + 1, np.int32)
            cu[1:] = np.cumsum(this)

            # fresh-prefill steps (every scheduled row starts at cache pos
            # 0) run the varlen-flash specialization: block-diagonal
            # attention over the packed tokens instead of the page-pool
            # gather
            fresh = self._compiled_fresh is not None \
                and not any(starts)
            compiled = self._compiled_fresh if fresh else self._compiled
            # bind the step's pinned weight version (_schedule guarantees
            # every scheduled row shares it); shapes/dtypes are identical
            # across versions so no retrace happens on a swap
            fp = self._params_for(rows[0][0].weight_version)
            program = "serving_fresh_prefill" if fresh else "serving_step"
            logits = self._run_step(program, compiled, fp, tokens, enc,
                                    dec, this, cu, bt, slots)
            self._count_step(note, program, len(rows), len(packed), n_pad,
                             prefill_tokens)
            for (r, chunk), cached in zip(rows, starts):
                self._maybe_register_prefix(r, cached + chunk)
            note["lookahead"] = int(flight is not None)
            if flight is not None:
                self._m.lookahead.inc()
            if self._row_state:
                m = self._m
                m.ssm_decode.inc(sum(c == 1 for _, c in rows))
                m.ssm_chunk.inc(sum(c > 1 for _, c in rows))
                m.ssm_resets.inc(sum(c == 0 for c in starts))
                # the step span says how many rows the state-update kernel
                # served: a reader prices the kernel's calls by it
                note["ssm_rows_decode"] = sum(c == 1 for _, c in rows)
            if self._states.step_args is not None:
                note.update(self._states.step_args(
                    [(c, chunk) for c, (_, chunk) in zip(starts, rows)]))

            # device-side sampling for rows that reached their sequence
            # tip (the token in flight is part of the sequence, and of the
            # count the salt is drawn from)
            temps = np.zeros(B1, np.float32)
            topks = np.zeros(B1, np.int32)
            topps = np.ones(B1, np.float32)
            salts = np.zeros(B1, np.int32)
            tip = [False] * len(rows)
            for i, (r, chunk) in enumerate(rows):
                ahead_tok = r.ahead_row >= 0
                if starts[i] + chunk == r.length + ahead_tok:
                    tip[i] = True
                    sp = r.sampling
                    temps[i] = sp.temperature
                    topks[i] = sp.top_k
                    topps[i] = sp.top_p
                    salts[i] = self._salt(r, len(r.generated) + ahead_tok)
        with _tracing.phase("serving::sample_sync"):
            # this step's sampler is enqueued behind it (a pure
            # prefill-chunk step samples nothing); then the wait for the
            # step before it, which ran while this one was packed
            sampled = counts = None
            if any(tip):
                sampled = self._sample_dev(logits, temps, topks, topps,
                                           salts)
                # a step that samples nothing leaves its model counts on
                # the device, added up, for the next that does
                counts, self._step_counts = self._step_counts, None
            before = self._fetch_flight()
        with _tracing.phase("serving::emit"):
            settled += self._emit_flight(before)
            for i, (r, chunk) in enumerate(rows):
                if not r.done:        # (an end of stream just seen)
                    r.ahead = chunk
                    r.ahead_row = i if tip[i] else -1
            self._flight = _Flight(rows, tip, sampled, counts)
        if self._drafter is not None:
            # a drafter reads settled tokens: this engine steps serially
            settled += self._settle_forced()
        return settled

    def _run_step(self, program, compiled, fp, tokens, enc, dec, this, cu,
                  bt, slots):
        """Run one step program over the engine's state and keep what it
        returns: (logits, pages, then the int8 scales or the row states,
        the page sides and the step's own counts). A from_model step was
        given its pages, scales, row state and page sides (donated): they
        are replaced, not copied."""
        if self._row_state or self._page_side:
            extra = (*self._row_state.values(), *self._page_side.values(),
                     slots)
        elif self._ks is not None:
            extra = (self._ks, self._vs)
        else:
            extra = ()
        args = (fp, self._buffers, tokens, enc, dec, this, cu, bt,
                self._kc, self._vc, *extra)
        self._register_program(program, compiled, args)
        out = compiled(*args)
        self._set_caches(out[1], out[2])
        if self._row_state or self._page_side:
            n = len(self._row_state)
            self._row_state = dict(zip(self._row_state, out[3:3 + n]))
            self._page_side = dict(zip(self._page_side, out[3 + n:]))
            n += len(self._page_side)
            if self._states.counters:
                # a step that samples nothing fetches nothing: its counts
                # wait, added up on the device, for the next that does
                self._step_counts = out[3 + n] if self._step_counts is None \
                    else self._step_counts + out[3 + n]
        elif self._ks is not None:
            self._ks, self._vs = out[3], out[4]
        return out[0]

    def _register_program(self, name, jitted, args, tok_len=None):
        """First call of a step program (of each token length, where it
        has several): keep how to compile it again (shapes and shardings
        of this call), so device time can be split by its named scopes.
        The entry is this engine's own (every engine of a process has a
        `serving_step`) and references the function weakly: the step's
        closure holds the model, and nothing may keep that alive once the
        engine and the model are dropped."""
        if (name, tok_len) not in self._programs:
            self._programs[name, tok_len] = _scopes.register_program(
                name, jitted, _scopes.abstract(args), weak=True)

    def phase_map(self, program="serving_step", tok_len=None):
        """{HLO instruction name: (pass, block)} of one of this engine's
        step programs once it has run, None before: `serving_step`,
        `serving_fresh_prefill`, or `serving_spec_verify` at one of its
        token lengths (`tok_len`, a verify shape this engine has run).
        Compiles the program again (a hit in the persistent compilation
        cache): ask after the window, while the engine lives."""
        prog = self._programs.get((program, tok_len))
        return prog.phases() if prog is not None else None

    @staticmethod
    def _sample_dev(logits, temps, topks, topps, salts):
        """Sampler dispatch; the tokens stay on the device. Fast paths:
        skip the full-vocab sort when no row samples, or when every
        sampling row fits the exact top-k candidate sampler."""
        if not np.any(temps > 0):
            return _greedy_tokens_dev(logits)
        if _topk_fast_ok(temps, topks):
            return _sample_topk_dev(logits, temps, topks, topps, salts)
        return _sample_tokens_dev(logits, temps, topks, topps, salts)

    @classmethod
    def _sample(cls, *args):
        """Sampler dispatch and the fetch of its tokens at once: a
        speculative step's wait for the chip."""
        return np.asarray(cls._sample_dev(*args))

    # -- speculative decode (draft k, verify in one paged step) ----------
    def _spec_step(self, rows, note):
        """One speculative iteration over decode-tip rows: the drafter
        proposes up to ``_spec_k`` tokens per row, the target model
        scores tip+drafts in ONE paged-attention dispatch (the verify
        chunk is shaped exactly like a chunked-prefill continuation),
        and every position is sampled under the salt the plain decode
        path would use at that generated index.  A draft is accepted
        only when it EQUALS the token the target sampled at the
        previous position, so the emitted stream is token-bitwise-
        identical to non-speculative decoding; KV pages holding only
        rejected-tail slots roll back to the pool, leaving each row at
        its decode tip (migratable/requeueable) after every step."""
        with _tracing.phase("serving::pack"):
            cfg = self.cfg
            B1 = cfg.max_batch + 1
            drafter = self._drafter

            # plan: per-row draft length, clamped to the remaining max_new
            # budget (later rows keep >= 1 slot each) and the page pool
            budget = cfg.token_budget
            avail = len(self._free_pages)
            if self._prefix_cache is not None:
                avail += self._prefix_cache.evictable_count()
            plans = []
            for idx, (r, _chunk) in enumerate(rows):
                self._spec_observe(r)
                rows_after = len(rows) - idx - 1
                cap = min(self._spec_k,
                          r.max_new - len(r.generated) - 1,
                          budget - 1 - rows_after)
                drafts = []
                if cap > 0:
                    proposed = drafter.propose(r.prompt + r.generated, cap)
                    for t in list(proposed)[:cap]:
                        t = int(t)
                        if not 0 <= t < cfg.vocab_size:
                            break      # alien draft vocab: stop the run
                        drafts.append(t)
                while drafts and max(
                        math.ceil((r.cached + 1 + len(drafts))
                                  / cfg.block_size) - len(r.pages),
                        0) > avail:
                    drafts.pop()       # page-limited: shorten the proposal
                avail -= max(math.ceil((r.cached + 1 + len(drafts))
                                       / cfg.block_size) - len(r.pages), 0)
                budget -= 1 + len(drafts)
                plans.append((r, drafts))

            enc = np.zeros(B1, np.int32)
            dec = np.zeros(B1, np.int32)
            this = np.zeros(B1, np.int32)
            bt = np.zeros((B1, cfg.max_blocks_per_seq), np.int32)
            packed = []
            spans = []
            for i, (r, drafts) in enumerate(plans):
                n_feed = 1 + len(drafts)
                dec[i] = r.cached
                this[i] = n_feed
                self._ensure_pages(r, r.cached + n_feed)
                bt[i, :len(r.pages)] = r.pages
                spans.append((len(packed), n_feed))
                packed.append((r.prompt + r.generated)[-1])
                packed.extend(drafts)
            self._update_pool_gauges(len(plans))
            # pad to a power-of-two token length (the trash row absorbs the
            # padding, exactly as in _step) so verify executables stay
            # bounded at log2(token_budget) shapes
            tok_len = self._fixed_token_len \
                or min(_next_pow2(len(packed)), cfg.token_budget)
            if tok_len not in self._spec_shapes:
                if self._spec_shapes:
                    from ..jit.api import note_retrace

                    note_retrace("spec_verify")
                self._spec_shapes.add(tok_len)
                self._m.fused_regions.inc()
            n_pad = tok_len - len(packed)
            this[B1 - 1] = n_pad
            enc[B1 - 1] = n_pad
            tokens = np.asarray(packed + [0] * n_pad, np.int32)
            cu = np.zeros(B1 + 1, np.int32)
            cu[1:] = np.cumsum(this)

            extra = (self._ks, self._vs) if self._ks is not None else ()
            args = (self._params_for(plans[0][0].weight_version),
                    self._buffers, tokens, enc, dec, this, cu,
                    bt, self._kc, self._vc, *extra)
            self._register_program("serving_spec_verify",
                                   self._compiled_verify, args, tok_len)
            out = self._compiled_verify(*args)
            self._count_step(note, "serving_spec_verify", len(plans),
                             len(packed), n_pad, 0)
            logits = out[0]                                # [tok_len, V]
            self._set_caches(out[1], out[2])
            if self._ks is not None:
                self._ks, self._vs = out[3], out[4]

            # sample EVERY fed position under its own schedule-independent
            # salt: position j of row r is generated-index g0+j, so the
            # draw equals what the plain path would make there
            P = len(packed)
            Pb = min(_next_pow2(max(P, 1)), tok_len)
            temps = np.zeros(Pb, np.float32)
            topks = np.zeros(Pb, np.int32)
            topps = np.ones(Pb, np.float32)
            salts = np.zeros(Pb, np.int32)
            for i, (r, _drafts) in enumerate(plans):
                p0, n_feed = spans[i]
                sp = r.sampling
                g0 = len(r.generated)
                for j in range(n_feed):
                    temps[p0 + j] = sp.temperature
                    topks[p0 + j] = sp.top_k
                    topps[p0 + j] = sp.top_p
                    salts[p0 + j] = self._salt(r, g0 + j)
        with _tracing.phase("serving::sample_sync"):
            sampled = self._sample(logits[:Pb], temps, topks, topps, salts)

        with _tracing.phase("serving::emit"):
            produced = []
            now = time.perf_counter()
            for i, (r, drafts) in enumerate(plans):
                p0, n_feed = spans[i]
                # accept the longest run of drafts matching the target's
                # own sampled choices; the first mismatch position still
                # yields its (correct) target-sampled token
                emitted = [int(sampled[p0])]
                for j in range(1, n_feed):
                    if drafts[j - 1] != emitted[-1]:
                        break
                    emitted.append(int(sampled[p0 + j]))
                self._spec_drafted_total += len(drafts)
                self._spec_accepted_total += len(emitted) - 1
                self._m.spec_drafted.inc(len(drafts))
                self._m.spec_accepted.inc(len(emitted) - 1)
                for t in emitted:
                    r.generated.append(t)
                    produced.append((r.rid, t))
                    self._note_first_token(r, now)
                    if len(r.generated) >= r.max_new \
                            or (r.eos_token_id is not None
                                and t == r.eos_token_id):
                        r.done = True
                        break
                # back to the decode tip: KV for the accepted run is valid;
                # pages holding only rejected-tail slots return to the pool
                r.cached = r.length - 1
                self._maybe_register_prefix(r)
                if r.done:
                    self._finish(r, now)
                else:
                    keep = math.ceil(r.cached / cfg.block_size)
                    if len(r.pages) > keep:
                        self._free_pages.extend(r.pages[keep:])
                        del r.pages[keep:]
            self._m.spec_steps.inc()
            self._m.tokens.inc(len(produced))
            if self._spec_drafted_total:
                self._m.spec_accept_rate.set(
                    self._spec_accepted_total / self._spec_drafted_total)
            if plans:
                self._m.spec_tokens_per_step.set(len(produced) / len(plans))
        return produced

    # -- multi-step decode (one device program per window) ---------------
    def _decode_window_fn(self, n_rows, n_steps, sample_mode):
        """Jitted whole-window decoder: `n_steps` model steps + sampling
        + next-token feed as ONE lax.scan on device — a decode window is
        a single dispatch + a single sync, so host/link latency is paid
        once per window instead of once per token (the reference serving
        stack's multi-step scheduling, done the XLA way)."""
        tok_len = self._fixed_token_len or n_rows
        key = (n_rows, n_steps, sample_mode, tok_len)
        fn = self._window_fns.get(key)
        if fn is not None:
            return fn
        if self._window_fns:
            # a SECOND distinct window shape on this engine is a
            # retrace of the fused decode region — the row-count
            # bucketing in _decode_run exists to keep these rare (the
            # regression test counts this cause)
            from ..jit.api import note_retrace

            note_retrace("decode_window")
        self._m.fused_regions.inc()
        B1 = self.cfg.max_batch + 1
        cache_dt = self._cache_dt
        compiled = self._compiled
        quant = self._ks is not None

        def window(fp, fb, tokens, enc, dec, this, cu, bt, kc, vc,
                   scales, temps, topks, topps, salts):  # salts [n, B1]
            live = (jnp.arange(B1) < n_rows).astype(jnp.int32)

            def body(carry, salts_j):
                tokens, dec, kc, vc, scales = carry
                out = compiled(fp, fb, tokens, enc, dec, this, cu, bt,
                               kc, vc, *scales)
                logits, kc, vc = out[0], out[1], out[2]
                scales = tuple(out[3:5]) if quant else ()
                kc = kc.astype(cache_dt)
                vc = vc.astype(cache_dt)
                if sample_mode == "topk":
                    sampled = _sample_topk_core(logits, temps, topks,
                                                topps, salts_j)
                elif sample_mode == "full":
                    sampled = _sample_core(logits, temps, topks, topps,
                                           salts_j)
                else:
                    sampled = jnp.argmax(logits, -1).astype(jnp.int32)
                tokens = jnp.concatenate(
                    [sampled[:n_rows],
                     jnp.zeros((tok_len - n_rows,), jnp.int32)])
                return (tokens, dec + live, kc, vc, scales), sampled

            (_, _, kc, vc, scales), samples = jax.lax.scan(
                body, (tokens, dec, kc, vc, scales), salts)
            return samples, kc, vc, scales

        # the pages and the int8 scales are the window's to update in
        # place: a from_model engine donates them, as its steps do
        donate = (8, 9, 10) if self._compiled_fresh is not None else ()
        fn = self._window_fns[key] = jax.jit(window, donate_argnums=donate)
        return fn

    def lower_fused_decode(self, n_rows=None):
        """StableHLO text of this engine's decode iteration lowered as a
        single auto-fused region via ``jit.lower_stablehlo(fn, spec,
        auto_fuse=True)`` — the inspectable compiler artifact of the
        whole-step decode executable ``_decode_window_fn`` dispatches.
        ``n_rows`` defaults to the full batch and is bucketed to the
        same pow2 grid ``_decode_run`` uses, so the dumped region
        matches the shape the engine actually traces."""
        from ..analysis.program.capture import decode_step_spec
        from ..jit.api import lower_stablehlo

        cfg = self.cfg
        rows = min(_next_pow2(n_rows or cfg.max_batch), cfg.max_batch)
        fn, spec = decode_step_spec(
            rows=rows, heads=cfg.num_heads, head_dim=cfg.head_dim,
            block_size=cfg.block_size,
            max_blocks=cfg.max_blocks_per_seq, n_pages=cfg.num_blocks,
            ffn=cfg.ffn_size, vocab=cfg.vocab_size)
        self._m.fused_regions.inc()
        return lower_stablehlo(fn, spec, name_prefix="decode",
                               auto_fuse=True)

    def decode_run(self, n_steps):
        """Run up to `n_steps` decode iterations over the current decode
        batch as one device-side scan (ONE dispatch + ONE host sync):
        each step's sampled tokens feed the next step's inputs on device.
        Requests must be at their decode tip (fully prefilled); pages for
        the whole window are reserved up front so block tables stay
        static. Returns the produced (rid, token) list in step order,
        after those of the step in flight, which it settles first."""
        if self._row_state:
            raise ValueError(
                "decode_run's fused window with a state-space layer: the "
                "window program threads pages only; step() serves this "
                "model")
        with RecordEvent("serving::decode_run"):
            produced = self._decode_run(n_steps)
        return self._take_held() + produced

    def _decode_run(self, n_steps):
        cfg = self.cfg
        self._check_alive()
        # the window reads every row's newest token: settle first
        settled = self._settle_forced() + self._evict_expired()
        rows = [r for r in self.pending()
                if r.length - r.cached == 1]
        if rows:
            # one weight version per window, oldest tip row's first —
            # same single-version dispatch contract as _schedule
            wv = rows[0].weight_version
            rows = [r for r in rows
                    if r.weight_version == wv][:cfg.max_batch]
        if not rows:
            return settled
        # same pre-mutation contract as _step: every selected row is at
        # its decode tip when a kill fires here, i.e. migratable
        self._fault_event("decode")
        n = min([n_steps] + [r.max_new - len(r.generated) for r in rows])
        # clamp the window to what the free page pool can hold (the whole
        # window's pages are reserved up front so block tables stay
        # static); callers fall back to step() — which can preempt — when
        # not even one decode step fits
        free = len(self._free_pages)
        while n > 0 and sum(
                max(math.ceil((r.cached + n) / cfg.block_size)
                    - len(r.pages), 0) for r in rows) > free:
            n -= 1
        if n <= 0:
            return settled
        if n < n_steps:
            # bound the executable zoo: tail windows (remaining budget or
            # page pool smaller than requested) round down to a power of
            # two, so at most log2 window programs exist per batch size
            # instead of one per distinct remaining-token count
            n = 1 << (n.bit_length() - 1)
        B = len(rows)
        B1 = cfg.max_batch + 1
        for r in rows:
            self._ensure_pages(r, r.cached + n)
            self._maybe_register_prefix(r)
        self._update_pool_gauges(B)
        self._m.steps.inc(n)

        # bucket the row count to a power of two so batch-size drift
        # between sweeps (requests finishing, new ones joining) reuses
        # the compiled window instead of retracing per distinct B; the
        # padded slots' tokens route to the trash row/page like any
        # other padding
        Bb = min(_next_pow2(B), cfg.max_batch)
        enc = np.zeros(B1, np.int32)
        this = np.zeros(B1, np.int32)
        this[:B] = 1
        # jit engines feed Bb live-bucket tokens (decode matmuls run at
        # T=Bb, not the full prefill budget); artifact engines must pad
        # to the module's fixed token length
        tok_len = self._fixed_token_len or Bb
        n_pad = tok_len - B
        this[B1 - 1] = n_pad
        enc[B1 - 1] = n_pad
        cu = np.zeros(B1 + 1, np.int32)
        cu[1:] = np.cumsum(this)
        # the window's n steps in the step account: B rows of one token
        self._m.step_rows.inc(B * n)
        self._m.step_tokens.inc(B * n)
        self._m.step_pad.inc(n_pad * n)
        bt = np.zeros((B1, cfg.max_blocks_per_seq), np.int32)
        for i, r in enumerate(rows):
            bt[i, :len(r.pages)] = r.pages
        dec0 = np.array([r.cached for r in rows], np.int32)
        ngen0 = [len(r.generated) for r in rows]

        tokens = np.asarray(
            [(r.prompt + r.generated)[-1] for r in rows]
            + [0] * n_pad, np.int32)
        temps = np.zeros(B1, np.float32)
        topks = np.zeros(B1, np.int32)
        topps = np.ones(B1, np.float32)
        for i, r in enumerate(rows):
            temps[i] = r.sampling.temperature
            topks[i] = r.sampling.top_k
            topps[i] = r.sampling.top_p
        if not np.any(temps > 0):
            sample_mode = "greedy"
        elif _topk_fast_ok(temps, topks):
            sample_mode = "topk"
        else:
            sample_mode = "full"
        salts = np.zeros((n, B1), np.int32)
        for j in range(n):
            for i, r in enumerate(rows):
                salts[j, i] = self._salt(r, ngen0[i] + j)
        dec = np.zeros(B1, np.int32)
        dec[:B] = dec0

        window = self._decode_window_fn(Bb, n, sample_mode)
        scales = (self._ks, self._vs) if self._ks is not None else ()
        samples, kc, vc, scales = window(
            self._params_for(rows[0].weight_version), self._buffers,
            tokens, enc, dec, this, cu, bt,
            self._kc, self._vc, scales, temps, topks, topps, salts)
        self._count_kernel_steps("serving_step", n)   # the window scans it
        self._kc, self._vc = kc, vc
        if self._ks is not None:
            self._ks, self._vs = scales
        fetched = np.asarray(samples)                    # [n, B1] — sync
        now = time.perf_counter()
        produced = []
        for j in range(n):
            for i, r in enumerate(rows):
                if r.done:
                    continue
                nxt = int(fetched[j, i])
                r.generated.append(nxt)
                r.cached += 1
                produced.append((r.rid, nxt))
                self._note_first_token(r, now)
                if len(r.generated) >= r.max_new \
                        or (r.eos_token_id is not None
                            and nxt == r.eos_token_id):
                    self._finish(r, now)
        self._m.tokens.inc(len(produced))
        return settled + produced

    def run_to_completion(self, max_steps=1000):
        for _ in range(max_steps):
            if not self.pending():
                break
            self.step()
        self.settle()       # max_steps ran out with a step in flight
        return {rid: list(r.generated)
                for rid, r in self._requests.items()}


def save_paged_model(path_prefix: str, model: PagedCausalLM):
    """Export the paged step function as a serving artifact with the
    engine's static shapes. Traced where it is called: off the chip (the
    usual place) the artifact holds XLA's scatter and the gathered
    reference attention, not the page-write and paged-attention kernels,
    and its engine counts no `serving/paged_kernel_steps` and no
    `serving/kv_inplace_steps`; its stacks are not donated."""
    from . import PrecisionType, save_inference_model
    from ..jit.api import InputSpec

    cfg = model.cfg
    B1 = cfg.max_batch + 1
    L = cfg.num_layers
    cache_shape = (L, cfg.num_blocks, cfg.num_kv_heads, cfg.block_size,
                   cfg.head_dim)
    spec = [
        InputSpec((cfg.token_budget,), "int32", "tokens"),
        InputSpec((B1,), "int32", "seq_lens_encoder"),
        InputSpec((B1,), "int32", "seq_lens_decoder"),
        InputSpec((B1,), "int32", "seq_lens_this_time"),
        InputSpec((B1 + 1,), "int32", "cu_seqlens_q"),
        InputSpec((B1, cfg.max_blocks_per_seq), "int32", "block_tables"),
        InputSpec(cache_shape, cfg.dtype, "key_caches"),
        InputSpec(cache_shape, cfg.dtype, "value_caches"),
    ]
    precision = PrecisionType.Bfloat16 if cfg.dtype == "bfloat16" \
        else PrecisionType.Float32
    return save_inference_model(path_prefix, model, spec,
                                precision=precision,
                                output_names=["logits", "key_caches",
                                              "value_caches"])

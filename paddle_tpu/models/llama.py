"""Llama model family — the flagship LLM (BASELINE configs: Llama-2 7B/13B
under TP x PP x sharding).

Reference analog: the reference trains Llama through PaddleNLP on top of the
fused ops this framework provides natively (fused_rms_norm, fused_rope,
flash attention — see incubate/nn/functional and ops/pallas).

Two coordinated implementations share the same math:

- **LlamaForCausalLM (nn.Layer)** — eager, define-by-run, TP-aware (uses
  Vocab/Column/RowParallelLinear when a model-parallel topology is active).
  This is the API-parity surface.

- **functional core (`forward_stacked`)** — the TPU-native compiled path:
  all transformer blocks' weights live STACKED with a leading layer axis and
  the trunk is ONE lax.scan over layers (+ jax.checkpoint per block). This
  is what makes whole-model compilation scale: constant compile time in
  depth, natural pipeline placement (stack axis sharded over 'pp'), FSDP
  (non-mp dim over 'sharding'), and TP (head/ffn dims over 'mp') — the
  sharding recipe of the scaling-book. `param_specs()` returns the
  PartitionSpec table the distributed trainer applies.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..incubate.nn.functional import fused_rotary_position_embedding, swiglu
from ..ops.pallas import flash_attention as fa
from ..ops.pallas import per_shard
from ..ops.pallas import ring_attention as ra
from ..ops.pallas import rms_norm as rn
from ..profiler.scopes import scope

__all__ = ["LlamaConfig", "LlamaForCausalLM", "LlamaModel",
           "forward_stacked", "loss_fn_stacked", "loss_fn_pipelined",
           "init_stacked_params", "param_specs", "microbatch_spec",
           "LLAMA_PRESETS"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    use_flash_attention: bool = True
    recompute: bool = True
    # remat policy for the stacked trunk: "full" recomputes the whole block
    # in backward; "save_attn" keeps flash-attention outputs (less refwd
    # compute, more HBM)
    remat_policy: str = "full"

    def __post_init__(self):
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


LLAMA_PRESETS = {
    "llama2-7b": LlamaConfig(),
    "llama2-13b": LlamaConfig(hidden_size=5120, intermediate_size=13824,
                              num_hidden_layers=40, num_attention_heads=40),
    "llama2-70b": LlamaConfig(hidden_size=8192, intermediate_size=28672,
                              num_hidden_layers=80, num_attention_heads=64,
                              num_key_value_heads=8),
    "tiny": LlamaConfig(vocab_size=512, hidden_size=256,
                        intermediate_size=512, num_hidden_layers=2,
                        num_attention_heads=4, max_position_embeddings=512),
    "debug": LlamaConfig(vocab_size=256, hidden_size=128,
                         intermediate_size=256, num_hidden_layers=2,
                         num_attention_heads=2, num_key_value_heads=2,
                         max_position_embeddings=256, dtype="float32"),
}


@functools.lru_cache(maxsize=1024)
def _position_ids(s, off):
    """Host-built position Tensor (a DYNAMIC dispatch leaf): the
    per-step int offset must not enter the op-cache key, or every decode
    position would mint a fresh cache entry. Memoized so an L-layer
    forward uploads ONE array per step, not L."""
    import numpy as np

    return Tensor(np.arange(s, dtype=np.int64).reshape(1, s) + off)


def _i64(v):
    """Loop counters enter ops as DYNAMIC scalars: a python int would
    bake into the dispatch-cache key, minting one entry per step. Under
    a lowered loop the counter arrives as a raw traced jax value."""
    import numpy as np

    if isinstance(v, Tensor):
        return v
    if isinstance(v, (jax.Array, jax.core.Tracer)):
        return Tensor(v)
    return Tensor(np.int64(v))


def _mp_active():
    from ..distributed.topology import get_hybrid_communicate_group

    hcg = get_hybrid_communicate_group()
    return hcg is not None and hcg.get_model_parallel_world_size() > 1


# ---------------------------------------------------------------------------
# eager nn.Layer implementation
# ---------------------------------------------------------------------------

class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        kvh = config.num_key_value_heads * config.head_dim
        if _mp_active():
            from ..distributed.meta_parallel import (ColumnParallelLinear,
                                                     RowParallelLinear)

            self.q_proj = ColumnParallelLinear(h, h, has_bias=False,
                                               gather_output=False)
            self.k_proj = ColumnParallelLinear(h, kvh, has_bias=False,
                                               gather_output=False)
            self.v_proj = ColumnParallelLinear(h, kvh, has_bias=False,
                                               gather_output=False)
            self.o_proj = RowParallelLinear(h, h, has_bias=False)
        else:
            self.q_proj = nn.Linear(h, h, bias_attr=False)
            self.k_proj = nn.Linear(h, kvh, bias_attr=False)
            self.v_proj = nn.Linear(h, kvh, bias_attr=False)
            self.o_proj = nn.Linear(h, h, bias_attr=False)

    def forward(self, x, kv_cache=None, position_offset=0):
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape(
            [b, s, cfg.num_attention_heads, cfg.head_dim])
        k = self.k_proj(x).reshape(
            [b, s, cfg.num_key_value_heads, cfg.head_dim])
        v = self.v_proj(x).reshape(
            [b, s, cfg.num_key_value_heads, cfg.head_dim])
        prev_len = 0
        if kv_cache is not None:
            prev_len = int(kv_cache[0].shape[1])
        # rope at ABSOLUTE positions: a decode chunk appended after
        # prev_len cached tokens rotates at prev_len..prev_len+s-1
        pos_ids = None
        if prev_len or position_offset:
            pos_ids = _position_ids(s, prev_len + position_offset)
        q, k, _ = fused_rotary_position_embedding(
            q, k, None, position_ids=pos_ids,
            rotary_emb_base=cfg.rope_theta)
        if kv_cache is not None:
            k_prev, v_prev = kv_cache
            from ..ops.manipulation import concat

            k = concat([k_prev, k], axis=1)
            v = concat([v_prev, v], axis=1)
            new_cache = (k, v)
        else:
            new_cache = None
        rep = cfg.num_attention_heads // cfg.num_key_value_heads
        if rep > 1:
            from ..ops.manipulation import repeat_interleave

            k = repeat_interleave(k, rep, axis=2)
            v = repeat_interleave(v, rep, axis=2)
        # causal whenever the query chunk spans >1 position (prefill with
        # or without a cache); a 1-token decode attends the full prefix
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=s > 1)
        out = out.reshape([b, s, cfg.hidden_size])
        out = self.o_proj(out)
        if new_cache is not None:
            return out, new_cache
        return out


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        if _mp_active():
            from ..distributed.meta_parallel import (ColumnParallelLinear,
                                                     RowParallelLinear)

            self.gate_proj = ColumnParallelLinear(h, i, has_bias=False,
                                                  gather_output=False)
            self.up_proj = ColumnParallelLinear(h, i, has_bias=False,
                                                gather_output=False)
            self.down_proj = RowParallelLinear(i, h, has_bias=False)
        else:
            self.gate_proj = nn.Linear(h, i, bias_attr=False)
            self.up_proj = nn.Linear(h, i, bias_attr=False)
            self.down_proj = nn.Linear(i, h, bias_attr=False)

    def forward(self, x):
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self._recompute = config.recompute

    def forward(self, x, kv_cache=None):
        def block(h):
            a = self.self_attn(self.input_layernorm(h))
            h = h + a
            m = self.mlp(self.post_attention_layernorm(h))
            return h + m

        if kv_cache is not None:
            a, new_cache = self.self_attn(self.input_layernorm(x), kv_cache)
            x = x + a
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        if self._recompute and self.training:
            from ..distributed.fleet.recompute import recompute

            return recompute(block, x)
        return block(x)


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        if _mp_active():
            from ..distributed.meta_parallel import VocabParallelEmbedding

            self.embed_tokens = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(config.vocab_size,
                                             config.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)

    def forward(self, input_ids, kv_caches=None):
        x = self.embed_tokens(input_ids)
        if self.config.dtype == "bfloat16":
            x = x.astype("bfloat16")
        new_caches = [] if kv_caches is not None else None
        for i, layer in enumerate(self.layers):
            if kv_caches is not None:
                x, c = layer(x, kv_caches[i])
                new_caches.append(c)
            else:
                x = layer(x)
        x = self.norm(x)
        if kv_caches is not None:
            return x, new_caches
        return x


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, labels=None, kv_caches=None):
        if kv_caches is not None:
            h, new_caches = self.model(input_ids, kv_caches)
        else:
            h = self.model(input_ids)
        if self.lm_head is not None:
            logits = self.lm_head(h.astype("float32"))
        else:
            from ..ops.linalg import matmul

            logits = matmul(h.astype("float32"),
                            self.model.embed_tokens.weight.astype("float32"),
                            transpose_y=True)
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]))
            return loss
        if kv_caches is not None:
            return logits, new_caches
        return logits

    @classmethod
    def from_preset(cls, name: str):
        import copy

        return cls(copy.deepcopy(LLAMA_PRESETS[name]))

    # -- greedy generation with KV cache (deployment parity) ---------------
    def generate(self, input_ids, max_new_tokens=32, eos_token_id=None):
        from ..core.autograd import no_grad
        from ..ops.manipulation import concat
        from ..ops.search import argmax

        with no_grad():
            self.eval()
            n_layers = self.config.num_hidden_layers
            b = input_ids.shape[0]
            empty = [
                (Tensor(jnp.zeros((b, 0, self.config.num_key_value_heads,
                                   self.config.head_dim), jnp.float32)),
                 Tensor(jnp.zeros((b, 0, self.config.num_key_value_heads,
                                   self.config.head_dim), jnp.float32)))
                for _ in range(n_layers)
            ]
            logits, caches = self.forward(input_ids, kv_caches=empty)
            out = input_ids
            cur = argmax(logits[:, -1], axis=-1).reshape([b, 1])
            for _ in range(max_new_tokens):
                out = concat([out, cur], axis=1)
                if eos_token_id is not None and bool(
                        (cur == eos_token_id).all()):
                    break
                logits, caches = self.forward(cur, kv_caches=caches)
                cur = argmax(logits[:, -1], axis=-1).reshape([b, 1])
            return out

    def generate_static(self, input_ids, max_new_tokens=32,
                        eos_token_id=None):
        """Compile-friendly greedy decode — the dy2static target
        (VERDICT r3 #5): a FIXED-size token buffer, the EOS early-exit as
        a `break` (lowered to a carried stop-flag in lax.while_loop), and
        traced write positions via put_along_axis. One executable under
        @to_static, plain Python semantics eagerly; matches `generate`
        token-for-token on the generated prefix. (KV-cached decoding at
        serving efficiency lives in inference/serving.py; this path
        recomputes the prefix each step.) Returns the [b, s0+max_new]
        buffer; positions beyond an EOS stop hold padding zeros."""
        from ..ops.creation import zeros
        from ..ops.manipulation import concat, put_along_axis, \
            take_along_axis
        from ..ops.search import argmax

        b = input_ids.shape[0]
        s0 = input_ids.shape[1]
        pad = zeros([b, max_new_tokens], dtype="int64")
        buf = concat([input_ids.astype("int64"), pad], axis=1)
        zero_idx = zeros([b, 1], dtype="int64")
        zero_read = zeros([b, 1, 1], dtype="int64")
        for i in range(max_new_tokens):
            logits = self.forward(buf)               # causal: tail inert
            read = zero_read + _i64(i + s0 - 1)
            last = take_along_axis(logits, read, axis=1)   # [b, 1, V]
            nxt = argmax(last, axis=-1)                    # [b, 1]
            buf = put_along_axis(buf, zero_idx + _i64(i + s0), nxt,
                                 axis=1)
            if eos_token_id is not None:
                if (nxt == eos_token_id).all():
                    break
        return buf


# ---------------------------------------------------------------------------
# functional stacked core (compiled path)
# ---------------------------------------------------------------------------

def init_stacked_params(config: LlamaConfig, key=None,
                        dtype=None) -> Dict[str, Any]:
    """Initialize the stacked-parameter pytree. Block params have leading
    axis num_hidden_layers."""
    key = key if key is not None else jax.random.key(0)
    d = jnp.bfloat16 if (dtype or config.dtype) == "bfloat16" else jnp.float32
    h, i, v = config.hidden_size, config.intermediate_size, config.vocab_size
    kvh = config.num_key_value_heads * config.head_dim
    L = config.num_hidden_layers
    ks = jax.random.split(key, 10)

    def norm_init(shape, k, scale=None):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(d)

    return {
        "embed": norm_init((v, h), ks[0], scale=0.02),
        "blocks": {
            "wq": norm_init((L, h, h), ks[1]),
            "wk": norm_init((L, h, kvh), ks[2]),
            "wv": norm_init((L, h, kvh), ks[3]),
            "wo": norm_init((L, h, h), ks[4]),
            "w_gate": norm_init((L, h, i), ks[5]),
            "w_up": norm_init((L, h, i), ks[6]),
            "w_down": norm_init((L, i, h), ks[7]),
            "ln_attn": jnp.ones((L, h), jnp.float32),
            "ln_mlp": jnp.ones((L, h), jnp.float32),
        },
        "final_norm": jnp.ones((h,), jnp.float32),
        "lm_head": norm_init((h, v), ks[8]),
    }


def param_specs(config: LlamaConfig) -> Dict[str, Any]:
    """PartitionSpecs over the hybrid mesh axes (SURVEY §2.5 mapping):
    - stack axis (layers) -> 'pp'   (pipeline placement)
    - head/ffn parallel dim -> 'mp' (tensor parallel)
    - the remaining large dim -> 'sharding' (ZeRO/FSDP)
    - embeddings vocab dim -> 'mp'
    """
    fsdp = "sharding"
    return {
        "embed": P("mp", None),
        "blocks": {
            "wq": P("pp", fsdp, "mp"),
            "wk": P("pp", fsdp, "mp"),
            "wv": P("pp", fsdp, "mp"),
            "wo": P("pp", "mp", fsdp),
            "w_gate": P("pp", fsdp, "mp"),
            "w_up": P("pp", fsdp, "mp"),
            "w_down": P("pp", "mp", fsdp),
            "ln_attn": P("pp", None),
            "ln_mlp": P("pp", None),
        },
        "final_norm": P(None),
        "lm_head": P(fsdp, "mp"),
    }


def _rope(q, k, theta):
    b, s, nh, hd = q.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    pos = jnp.arange(s, dtype=jnp.float32)
    freqs = jnp.outer(pos, inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos = jnp.cos(emb)[None, :, None, :]
    sin = jnp.sin(emb)[None, :, None, :]

    def rot(t):
        d2 = t.shape[-1] // 2
        t1, t2 = t[..., :d2], t[..., d2:]
        rotated = jnp.concatenate([-t2, t1], axis=-1)
        return (t.astype(jnp.float32) * cos
                + rotated.astype(jnp.float32) * sin).astype(t.dtype)

    return rot(q), rot(k)


# layout of the trunk's [..., B, S, H] activations over the hybrid mesh,
# for the kernels' per-shard calls (ops.pallas.per_shard): batch over the
# data axes, sequence over 'sep', hidden whole
_ACT_SPEC = (("dp", "sharding"), "sep", None)


def _rms_norm(x, weight, eps, mesh):
    """rms_norm of [..., B, S, H] activations; per shard on a multi-device
    mesh (rows are independent, so any row sharding is exact)."""
    spec = P(*(None,) * (x.ndim - 3), *_ACT_SPEC)
    return per_shard(lambda a, w: rn.rms_norm(a, w, eps), mesh,
                     (spec, P(None)), spec)(x, weight)


def _block(params, x, config: LlamaConfig, mesh=None):
    """One decoder block on raw arrays (used inside lax.scan). `mesh` is
    the mesh the enclosing jit runs over, if any: on several devices
    rms-norm and attention run per shard (ops.pallas.per_shard), every
    other op under GSPMD. When its 'sep' axis is >1, attention runs as a
    ring over the sequence shards (ops/pallas/ring_attention: ppermute of
    K/V blocks with online-softmax merge and a hand-written ring VJP).
    This is the TPU-native SEP/context-parallel engine (SURVEY §2.5
    segment_parallel.py:26; the reference delegates ring-style attention
    to fused kernels + sep process groups). Inside the pipeline's 'pp'
    ring it attends over the gathered sequence instead."""
    h = config.hidden_size
    nh, kvh, hd = (config.num_attention_heads, config.num_key_value_heads,
                   config.head_dim)
    b, s, _ = x.shape

    from jax.ad_checkpoint import checkpoint_name

    # the ring asks axis_index('sep'), which jax 0.9 cannot lower in a
    # region nested inside another manual region (the pipeline's, over 'pp')
    ring = (mesh is not None and mesh.shape.get("sep", 1) > 1
            and not jax.sharding.get_abstract_mesh().manual_axes)

    def attend(qq, kk, vv):
        if ring:
            return ra.ring_attention_bshd(qq, kk, vv, axis_name="sep",
                                          is_causal=True)
        return fa.flash_attention_bshd(qq, kk, vv, is_causal=True)

    with scope("attention"):
        hx = _rms_norm(x, params["ln_attn"], config.rms_norm_eps, mesh)
        q = (hx @ params["wq"]).reshape(b, s, nh, hd)
        k = (hx @ params["wk"]).reshape(b, s, kvh, hd)
        v = (hx @ params["wv"]).reshape(b, s, kvh, hd)
        q, k = _rope(q, k, config.rope_theta)
        if nh != kvh:
            rep = nh // kvh
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        # [B, S, heads, D]; batch and heads are independent: each device
        # attends for its own (batch, heads) shard — as a ring over the
        # sequence shards, or over the whole sequence
        spec = P(("dp", "sharding"), "sep" if ring else None, "mp", None)
        attn = per_shard(attend, mesh, (spec,) * 3, spec)(q, k, v)
        attn = checkpoint_name(attn, "flash_attn_out")
        x = x + attn.reshape(b, s, h) @ params["wo"]

    with scope("mlp"):
        hx = _rms_norm(x, params["ln_mlp"], config.rms_norm_eps, mesh)
        gated = jax.nn.silu(hx @ params["w_gate"]) * (hx @ params["w_up"])
        x = x + gated @ params["w_down"]
    return x


def _trunk(params, input_ids, config: LlamaConfig, remat: bool = True,
           mesh=None):
    """Embedding -> lax.scan over stacked blocks (constant compile time in
    depth; blocks rematerialized in backward when remat=True). The single
    source of the trunk pattern for the stacked forward/loss paths."""
    with scope("embed"):
        x = jnp.take(params["embed"], input_ids, axis=0)
        if config.dtype == "bfloat16":
            x = x.astype(jnp.bfloat16)

    def body(carry, layer_params):
        return _block(layer_params, carry, config, mesh=mesh), None

    if remat:
        # "save_attn": keep each block's flash-attention output across the
        # backward so the refwd skips the attention recompute (~22% of fwd
        # FLOPs at 4k seq) for O(L*B*S*H) extra HBM.
        policy = (jax.checkpoint_policies.save_only_these_names(
            "flash_attn_out") if config.remat_policy == "save_attn"
            else None)
        body_fn = jax.checkpoint(body, policy=policy)
    else:
        body_fn = body
    x, _ = jax.lax.scan(body_fn, x, params["blocks"])
    return x


def forward_stacked(params, input_ids, config: LlamaConfig,
                    remat: bool = True):
    """Whole-model forward: trunk -> final norm -> logits."""
    x = _trunk(params, input_ids, config, remat)
    x = rn.rms_norm(x, params["final_norm"], config.rms_norm_eps)
    logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    return logits


def _head_loss(params, h, labels, config: LlamaConfig, mesh=None):
    """Shared tail of both training paths: final norm -> LM head ->
    mean next-token NLL. h: [..., S, H], labels: [..., S]."""
    with scope("head_loss"):
        h = _rms_norm(h, params["final_norm"], config.rms_norm_eps, mesh)
        logits = h.astype(jnp.float32) \
            @ params["lm_head"].astype(jnp.float32)
        # lse - picked, not log_softmax: avoids materializing a second
        # [.., S, V] fp32 array (reductions fuse into one pass over logits)
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
        picked = jnp.take_along_axis(
            logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(lse - picked)


def loss_fn_stacked(params, batch, config: LlamaConfig, remat: bool = True,
                    mesh=None):
    """Next-token LM loss; batch = (input_ids[B,S], labels[B,S]). Pass the
    `mesh` the step is jitted over: a 'sep' axis >1 runs ring-attention
    context parallel, and on any multi-device mesh the Pallas kernels run
    per shard (ops.pallas.per_shard)."""
    input_ids, labels = batch
    x = _trunk(params, input_ids, config, remat, mesh=mesh)
    return _head_loss(params, x, labels, config, mesh=mesh)


def microbatch_spec():
    """Sharding of a micro-batched tensor [n_micro, mb, S]: micro axis
    replicated (it is the pipeline's time axis), batch over the data axes,
    sequence over 'sep'."""
    return P(None, ("dp", "sharding"), "sep")


def loss_fn_pipelined(params, batch, config: LlamaConfig, mesh,
                      remat: bool = True, overlap_sends: bool = False):
    """Schedule-driven compiled pipeline loss over the 'pp' mesh axis.

    Reference analog: PipelineParallel.forward_backward_pipeline (1F1B,
    fleet/meta_parallel/pipeline_parallel.py:459) + the static pipeline
    scheduler passes. TPU-native shape: the trunk runs inside shard_map
    manual over 'pp' ONLY (dp/sharding/sep/mp stay GSPMD-auto), as a
    collective-permute micro-batch ring (spmd_pipeline): each of the
    n_micro + P - 1 ticks computes this stage's layer slice on its current
    micro-batch and ppermutes the activation one hop forward over ICI.
    jax.grad transposes the scan+ppermute into the reverse pipeline, so
    backward is an equally real schedule (GPipe ordering; bubble
    2(P-1)/(2M+2(P-1))). Embedding and the LM head run under plain GSPMD
    outside the ring (they are not layer-striped in the reference either).

    batch = (input_ids[n_micro, mb, S], labels[n_micro, mb, S]); `mesh`
    is the hybrid mesh (distributed.topology.build_mesh): inside the ring
    the Pallas kernels run per shard over its other axes
    (ops.pallas.per_shard nests in the 'pp' region).
    Requires num_hidden_layers % pp == 0.  ``overlap_sends=True``
    half-splits each tick's micro-batch so the first half's ICI hop
    overlaps the second half's block compute (latency-hidden pipeline
    sends; the same rows through the same products, bit for bit where
    the backend's product does not change with the row count — see
    ``spmd_pipeline``).
    """
    from ..distributed.meta_parallel.pipeline_parallel import spmd_pipeline

    input_ids, labels = batch
    n_micro = input_ids.shape[0]
    with scope("embed"):
        x = jnp.take(params["embed"], input_ids, axis=0)  # [NM, mb, S, H]
        if config.dtype == "bfloat16":
            x = x.astype(jnp.bfloat16)

    def stage_fn(stage_blocks, h):
        def body(c, bp):
            return _block(bp, c, config, mesh=mesh), None

        body_fn = jax.checkpoint(body) if remat else body
        y, _ = jax.lax.scan(body_fn, h, stage_blocks)
        return y

    def ring(stage_blocks, xm):
        p = jax.lax.axis_size("pp")
        stage = jax.lax.axis_index("pp")
        ys = spmd_pipeline(stage_fn, stage_blocks, xm, n_micro,
                           axis_name="pp", overlap_sends=overlap_sends)
        # replicate the last stage's finished micro-batches across 'pp' so
        # the head/loss run under plain GSPMD afterwards
        return jax.lax.psum(
            jnp.where(stage == p - 1, ys, jnp.zeros_like(ys)), "pp")

    block_specs = jax.tree.map(lambda _: P("pp"), params["blocks"])
    ys = jax.shard_map(
        ring, mesh=mesh, in_specs=(block_specs, P()), out_specs=P(),
        axis_names={"pp"}, check_vma=False)(params["blocks"], x)
    return _head_loss(params, ys, labels, config, mesh=mesh)
